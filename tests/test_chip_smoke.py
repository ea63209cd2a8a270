"""``chip_smoke.py``: refuses to report success off the chip, and its flow
runs end to end on the CPU at a tiny size in interpret mode."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.configs.base import ArchConfig, dense_stack  # noqa: E402
from repro.models.model import init_params  # noqa: E402

from model_ref import GreedyReference  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

# a tiny GQA config (2 query heads per KV head); f32 so the paged path and
# the reference forward agree token for token
TINY = ArchConfig(name="smoke-tiny", d_model=32, n_heads=4, n_kv_heads=2,
                  d_ff=64, vocab=64, groups=dense_stack(2), remat="none",
                  dtype="float32", act="gelu")
TINY_RUN = dict(prompt_lens=(12, 8, 10, 4, 7, 11, 5, 9), page=4,
                num_pages=64, prefill_chunk=4, max_new=3, seed=0)


@pytest.fixture(scope="module")
def tiny_run():
    lines = []
    res = chip_smoke.run_smoke(TINY, "interpret", log=lines.append, **TINY_RUN)
    return res, lines


def test_main_fails_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_and_prints_no_result(tmp_path):
    """Run from a directory holding only the script: no repo, no chip."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_flow_serves_tiny_config_in_interpret_mode(tiny_run):
    res, lines = tiny_run
    assert res["failures"] == [], res["failures"]
    assert res["kernel_impl"] == "interpret"
    assert res["completed"] == len(TINY_RUN["prompt_lens"])
    assert res["uaf"] == 0 and res["no_leaks"]
    assert res["max_abs_diff"] <= chip_smoke.LOGIT_TOL * res["ref_max_abs"]
    assert any(line.startswith("requests:") for line in lines)


def test_flow_checks_every_generated_token(tiny_run):
    res, _ = tiny_run
    n = len(TINY_RUN["prompt_lens"]) * TINY_RUN["max_new"]
    assert res["tokens_checked"] == n
    assert res["tokens_top1"] == n          # f32: no near ties flipped


def test_token_check_catches_a_wrong_row(tiny_run):
    """A batch row other than request 0 that emits a token far down the
    reference's ranking fails the smoke."""
    res, _ = tiny_run
    params = init_params(TINY, jax.random.PRNGKey(TINY_RUN["seed"]))
    prompts = chip_smoke.make_prompts(TINY.vocab, TINY_RUN["prompt_lens"],
                                      TINY_RUN["page"], TINY_RUN["seed"])
    outputs = [list(o) for o in res["outputs"]]
    ref = chip_smoke._reference_logits(params, TINY, prompts, outputs)
    outputs[5][1] = int(ref[5, 1].argmin())
    out = {"failures": [], "outputs": outputs}
    chip_smoke._check_against_reference(
        params, TINY, prompts, out, page=TINY_RUN["page"],
        chunk=TINY_RUN["prefill_chunk"], impl="interpret", log=lambda _: None)
    assert any(f.startswith("request 5 step 1:") for f in out["failures"])


def test_flow_tokens_match_dense_path(tiny_run):
    """The page layout is a storage change, not a model change: the
    smoke's paged tokens equal a greedy decode by the reference forward."""
    res, _ = tiny_run
    params = init_params(TINY, jax.random.PRNGKey(TINY_RUN["seed"]))
    prompts = chip_smoke.make_prompts(TINY.vocab, TINY_RUN["prompt_lens"],
                                      TINY_RUN["page"], TINY_RUN["seed"])
    ref = GreedyReference(params, TINY, max_new=TINY_RUN["max_new"])
    assert [ref(p) for p in prompts] == res["outputs"]
