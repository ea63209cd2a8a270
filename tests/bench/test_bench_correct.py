"""The comparison that decides ``correct``, shown to fail: a token altered
where it is produced, and the program computing in float8, each make a
smoke-size run come out not correct; the plain reference computes the
program's model; the float8 control reads far above the served path."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
#: the limit at smoke size (3 layers of width 72 or 64, vocab 256), set as
#: the cell's is: sound runs read up to 0.037 with a whole 8 s window
#: checked (bf16 near ties against the float32 reference), the program in
#: float8 read 0.12-0.34 (three seeds each)
SMOKE_LIMIT = 0.07


def _run(cell, seed=2**31 + 3):
    from bench.harness import run_cell

    cell.workload["limits"] = dict(cell.workload["limits"],
                                   max_logit_gap=SMOKE_LIMIT)
    return run_cell(cell, seed, 8.0, False, allow_cpu=True,
                    log=lambda s: None)


def test_sound_smoke_run_is_correct(smoke_cell):
    res = _run(smoke_cell("sc2-decode"))
    assert res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] < SMOKE_LIMIT


def test_a_token_altered_where_it_is_produced_fails(smoke_cell, monkeypatch):
    """Every second decode step hands the worker each row's worst token."""
    from repro.serve import paged_model

    orig = paged_model.paged_decode_step
    calls = {"n": 0}

    def altered(params, cfg, store, blocks, lens, last, *, impl):
        logits = orig(params, cfg, store, blocks, lens, last, impl=impl)
        calls["n"] += 1
        if calls["n"] % 2:
            return logits
        flipped = -logits
        return flipped.at[:, cfg.vocab:].set(-1e30)

    monkeypatch.setattr(paged_model, "paged_decode_step", altered)
    res = _run(smoke_cell("sc2-decode"))
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > SMOKE_LIMIT


@pytest.mark.parametrize("config", ["starcoder2-7b-L16", "stablelm-12b-L12"])
def test_the_program_in_float8_fails(smoke_cell, monkeypatch, config):
    """The served path with every layer's weights rounded through float8
    (e4m3) -- the precision below the configuration's bfloat16 -- under
    the decode mix, for each configuration file."""
    from repro.serve import paged_model
    from smoke_cells import SMOKE_MODELS

    def fp8(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(
            a.dtype) if a.ndim >= 2 else a, tree)

    # weights, and the activations entering the projections
    q_orig, o_orig = paged_model._qkv, paged_model._out_mlp
    monkeypatch.setattr(
        paged_model, "_qkv",
        lambda gp, rep, x, pos, **k: q_orig(fp8(gp), rep, fp8(x), pos, **k))
    monkeypatch.setattr(
        paged_model, "_out_mlp",
        lambda gp, rep, x, attn, **k: o_orig(fp8(gp), rep, x, fp8(attn), **k))
    orig_logits = paged_model._logits
    monkeypatch.setattr(
        paged_model, "_logits",
        lambda n, head, x, **k: orig_logits(n, fp8(head), x, **k))
    cell = smoke_cell("sc2-decode")
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    cell.config = dict(cfg, smoke=True, model=SMOKE_MODELS[cfg["registry"]],
                       engine=cell.config["engine"])
    # check every token of the window: at this size float8 moves few
    # top-1 choices, so a small sample can miss them
    cell.workload["limits"] = dict(cell.workload["limits"], check_tokens=1000)
    res = _run(cell)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > SMOKE_LIMIT


@pytest.mark.parametrize("registry", ["starcoder2_7b", "stablelm_12b"])
def test_reference_computes_the_programs_model(registry):
    """In float32 the plain reference and the program's own dense forward
    give the same logits on the benchmark's weights."""
    from bench import reference, weights
    from smoke_cells import SMOKE_MODELS
    from repro.configs.base import dense_stack
    from repro.configs.registry import get_smoke_config
    from repro.models.model import apply_model

    m = dict(SMOKE_MODELS[registry], dtype="float32")
    cfg = get_smoke_config(registry).scaled(groups=dense_stack(m["layers"]),
                                            dtype="float32")
    params = weights.make_params(m, 5, "float32")
    rng = np.random.default_rng(0)
    rows = [rng.integers(1, m["vocab"], n).tolist() for n in (40, 23)]
    picks = [list(range(len(r))) for r in rows]
    ref = reference.forward_logits(params, m, rows, picks)
    for r, want in zip(rows, ref):
        got = apply_model(params, jnp.asarray([r]), cfg=cfg,
                          mode="train")[0][0, :, :m["vocab"]]
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4,
                                   rtol=2e-4)


def test_float8_control_reads_above_the_served_path():
    """The control (the reference in float8) against the float32
    reference, next to the reference's own greedy tokens (gap 0)."""
    from bench import reference, weights
    from smoke_cells import SMOKE_MODELS

    m = SMOKE_MODELS["starcoder2_7b"]
    params = weights.make_params(m, 9)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, m["vocab"], n).tolist() for n in (30, 50)]
    samples = []
    for p in prompts:                       # greedy outputs of the reference
        out = []
        for _ in range(8):
            row = reference.fed_tokens(p, out + [0])
            lg = reference.forward_logits(params, m, [row],
                                          [[len(row) - 1]])[0]
            out.append(int(lg[-1].argmax()))
        samples.append({"prompt": p, "out": out})
    assert reference.served_gaps(params, m, samples).max() == 0.0
    assert reference.control_gaps(params, m, samples).max() > SMOKE_LIMIT


def test_fed_tokens_refeed_the_last_prompt_token():
    from bench.reference import fed_tokens

    assert fed_tokens([5, 6, 7], [1, 2, 3]) == [5, 6, 7, 7, 1, 2]


def test_new_files_are_found_by_name(tmp_path):
    """A throwaway configuration, mix, cell and metric, added as files in a
    copy of the benchmark, run with no edit to the harness: an open-loop
    chat cell on the chat-shared mix at smoke size, which also reports the
    open-loop readers (time to first token, goodput)."""
    from bench.harness import run_cell
    from bench.registry import load_cell
    from smoke_cells import SMOKE_MODELS, shrink

    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench / "configs" /
                      "starcoder2-7b-L16.json").read_text())
    (bench / "configs" / "tiny-test.json").write_text(json.dumps(
        dict(cfg, name="tiny-test")))
    mix = json.loads((bench / "traffic" / "chat-shared.json").read_text())
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    wl = {"config": "tiny-test", "traffic": "tiny-mix", "rate_rps": 1.5,
          "warmup_s": 6, "slo": {"ttft_s": 60.0, "gap_s": 60.0},
          "limits": {"max_logit_gap": SMOKE_LIMIT, "check_tokens": 24},
          "why": "a throwaway cell"}
    (bench / "workloads" / "tiny-cell.json").write_text(json.dumps(wl))
    (bench / "metrics" / "prompt_tokens_due.py").write_text(
        "def read(run):\n"
        "    return sum(len(t.req.prompt) for t in run.window_requests())\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-cell", "config": "tiny-test",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "a throwaway cell"})
    for name, unit in (("prompt_tokens_due", "tokens"), ("ttft_p90_s", "s"),
                       ("goodput_tok_s", "tokens/s")):
        spec["end_to_end"].append({"name": name, "unit": unit,
                                   "better": "lower", "bound": 0.25,
                                   "source": "host_clock",
                                   "workloads": ["tiny-cell"]})
    cell = load_cell("tiny-cell", bench, spec)
    assert cell.config["name"] == "tiny-test"
    assert cell.traffic["name"] == "tiny-mix"
    names = [m["name"] for m in cell.end_to_end]
    assert {"prompt_tokens_due", "ttft_p90_s", "setup_s"} <= set(names)
    assert "prompt_tokens_due" not in [
        m["name"] for m in load_cell("sc2-decode", bench, spec).end_to_end]
    shrink(cell)
    res = run_cell(cell, 77, 8.0, False, allow_cpu=True, log=lambda s: None)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert got["prompt_tokens_due"]["value"] > 0
    assert got["ttft_p90_s"]["value"] > 0 and "goodput_tok_s" in got
    assert "setup_s" in got


def test_program_runs_the_files_rope_base_and_norm_epsilon():
    from bench.harness import program_config
    from bench.registry import load_cell

    cell = load_cell("sc2-decode")
    cfg = program_config(cell)
    assert cfg.rope_theta == 1e6 == cell.config["rope_theta"]
    assert cfg.norm_eps == 1e-5 == cell.config["norm_epsilon"]
    assert cfg.n_layers == cell.model["layers"] == 16
