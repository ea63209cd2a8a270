"""forward_launches (paged forward): mean ``launches`` of the ``dispatch``
spans of the window's engine steps -- the programs one paged decode step
launches, kernel calls included (program spans).  A program whose spans
carry no such count reads nothing."""

from bench import spans


def read(run):
    n = [x.args["launches"] for s in spans.window_steps(run)
         for x in s.walk() if x.name == "dispatch" and "launches" in x.args]
    if not n:
        return None
    return sum(n) / len(n)
