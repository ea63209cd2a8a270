"""The cells at smoke size, for the benchmark's CPU tests.

A smoke cell keeps the cell's mix, loop and engine path but runs the
configuration's ``SMOKE`` variant with short prompts and a small pool, so
a whole run takes seconds on the CPU with the kernels in interpret mode.
"""

#: the registry's SMOKE variants, as the cells' ``model`` groups
SMOKE_MODELS = {
    "starcoder2_7b": dict(layers=3, d_model=72, n_heads=6, n_kv_heads=2,
                          head_dim=12, d_ff=144, vocab=256, act="gelu",
                          qk_norm=False, rope_pct=1.0, rope_theta=10000.0,
                          norm_eps=1e-6, dtype="bfloat16"),
    "stablelm_12b": dict(layers=3, d_model=64, n_heads=4, n_kv_heads=2,
                         head_dim=16, d_ff=128, vocab=256, act="silu",
                         qk_norm=True, rope_pct=0.25, rope_theta=10000.0,
                         norm_eps=1e-6, dtype="bfloat16"),
}


def shrink(cell):
    """``cell`` at smoke size, in place: the SMOKE model, a 256-page pool,
    batch 4, chunk 8, prompts and answers of a few tokens, and a closed
    loop's warmed decode and fill shapes cut to match."""
    cfg = cell.config
    cell.config = dict(cfg, smoke=True, model=SMOKE_MODELS[cfg["registry"]],
                       engine=dict(cfg["engine"], num_pages=256, max_batch=4,
                                   prefill_chunk=8))
    t = cell.traffic
    if t["loop"] == "open":
        cell.traffic = dict(
            t, tenants=dict(t["tenants"], count=2, prefix_tokens=32),
            prompt_len=dict(t["prompt_len"], median=8, lo=2, hi=24),
            output_len=dict(t["output_len"], median=4, lo=2, hi=6))
        cell.workload = dict(cell.workload, rate_rps=1.5)
    else:
        cell.traffic = dict(t, clients=4, requests=64,
                            prompt_len=dict(kind="uniform", lo=4, hi=12),
                            output_len=dict(kind="uniform", lo=2, hi=5))
        cell.workload = dict(cell.workload,
                             warm_decode=dict(rows=[3, 4], pages=[1, 3]),
                             warm_fill_pages=[1, 8])
    cell.workload = dict(cell.workload, warmup_s=6.0,
                         limits=dict(cell.workload["limits"],
                                     check_tokens=24))
    return cell

