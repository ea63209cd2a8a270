"""Compile the paged serving path for a TPU v5e without one.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology, and refuses what the chip's
compiler would refuse -- a block shape that breaks Mosaic's tiling rule, a
kernel over its VMEM budget, a program larger than the device's memory.
Interpret-mode tests cannot see any of that.

Covered, at the published widths of two configs the paged path serves
(bf16 pages, 16-token pages):

* the paged-attention kernel and the page write at starcoder2-7b (Hkv=4,
  D=128) and stablelm-12b (Hkv=8, D=160), the kernel also at decode table
  widths 21 and 44, the write donating the pool into its output;
* the program between two kernel calls at both widths, whose donated
  page arrays must alias its outputs;
* one whole paged decode step of starcoder2-7b cut to 16 layers, against
  a full 4096-page pool, whose ``memory_analysis()`` must fit one v5e's
  16 GiB, and one of stablelm-12b cut to 12, which must leave room.

The topology is described inside module fixtures, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import contextlib
import functools
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import dense_stack  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.paged_attention import paged_attention_pallas  # noqa: E402
from repro.models.model import abstract_params  # noqa: E402
from repro.runtime.kv_store import _set_rows, kv_layer_order  # noqa: E402
from repro.serve.paged_model import (_mlp_qkv, _out_mlp, _qkv,  # noqa: E402
                                     paged_decode_step)

GiB = 1 << 30
PAGE = 16
POOL_PAGES = 4096
DECODE_LAYERS = 16

# (name, n_heads, n_kv_heads, head_dim) -- the published widths
WIDTHS = [("starcoder2-7b", 36, 4, 128), ("stablelm-12b", 32, 8, 160)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU library loads below; without this it logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-topology compile can be written to the persistent cache
    # but never read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("name,H,Hkv,D", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_paged_attention_compiles_for_v5e(one_chip, name, H, Hkv, D):
    B, max_pages = 8, 32
    args = (_sds((B, H, D), "float32", one_chip),            # q (served f32)
            _sds((POOL_PAGES, PAGE, Hkv * D), "bfloat16", one_chip),
            _sds((POOL_PAGES, PAGE, Hkv * D), "bfloat16", one_chip),
            _sds((B, max_pages), "int32", one_chip),
            _sds((B,), "int32", one_chip))
    compiled = jax.jit(paged_attention_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", [21, 44])
@pytest.mark.parametrize("name,H,Hkv,D", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_paged_attention_compiles_at_decode_table_widths(one_chip, name, H,
                                                         Hkv, D, width):
    """A 32-row decode batch over tables as wide as the benchmark's
    (20-44 pages), neither a multiple of the kernel's block of pages."""
    B = 32
    args = (_sds((B, H, D), "float32", one_chip),
            _sds((POOL_PAGES, PAGE, Hkv * D), "bfloat16", one_chip),
            _sds((POOL_PAGES, PAGE, Hkv * D), "bfloat16", one_chip),
            _sds((B, width), "int32", one_chip),
            _sds((B,), "int32", one_chip))
    compiled = jax.jit(paged_attention_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,H,Hkv,D", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_paged_scatter_compiles_for_v5e(one_chip, name, H, Hkv, D):
    """The store's page write of one prefill chunk of tokens, jitted with
    the pool donated as the store runs it: it compiles for the chip, and
    the pool aliases its output, so the write is in place."""
    T = 64                                   # one prefill chunk of tokens
    fn = jax.jit(_set_rows, donate_argnums=(0,))
    compiled = fn.lower(_sds((POOL_PAGES, PAGE, Hkv * D), "bfloat16",
                             one_chip),
                        _sds((T,), "int32", one_chip),
                        _sds((T,), "int32", one_chip),
                        _sds((T, Hkv, D), "bfloat16", one_chip)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == \
        POOL_PAGES * PAGE * Hkv * D * 2


@pytest.mark.parametrize("name,layers,parallel",
                         [("starcoder2_7b", DECODE_LAYERS, False),
                          ("stablelm_12b", 12, True)],
                         ids=["starcoder2-7b", "stablelm-12b"])
def test_fused_layer_program_aliases_its_pages_on_v5e(one_chip, name, layers,
                                                      parallel):
    """The program between two kernel calls (a layer's MLP, the next
    layer's projections and page write), compiled for the chip at full
    width over a 4096-page pool: both donated page arrays alias its
    outputs, so the write moves a token's rows and not the pool."""
    cfg = get_config(name).scaled(groups=dense_stack(layers,
                                                     parallel=parallel))
    B, rows = 32, 32
    H, hd = cfg.n_heads, cfg.head_dim_
    gp = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                      abstract_params(cfg)["groups"]["g0"]["p0"])
    i32 = functools.partial(_sds, dtype="int32", sharding=one_chip)
    page = _sds((POOL_PAGES, PAGE, cfg.n_kv_heads * hd), cfg.dtype, one_chip)
    compiled = _mlp_qkv.lower(
        gp, i32(()), _sds((B, 1, cfg.d_model), cfg.dtype, one_chip),
        _sds((rows, H, hd), "float32", one_chip), gp, i32(()), i32((B, 1)),
        (page, page), i32((B,)), i32((B,)), cfg=cfg, rows=rows,
        parallel=parallel, out_mlp=_out_mlp, qkv=_qkv).compile()
    page_bytes = POOL_PAGES * PAGE * cfg.n_kv_heads * hd * 2
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * page_bytes


class _TracedPages:
    """The page-store surface ``paged_decode_step`` uses, over functional
    page arrays, so the whole step traces into one program."""

    def __init__(self, cfg, k, v):
        self.layer_order = kv_layer_order(cfg)
        self.page = PAGE
        self.k, self.v = list(k), list(v)

    def write_guard(self):
        return contextlib.nullcontext()

    def write_in(self, layer, tokens, program):
        self.k[layer], self.v[layer], out = program(self.k[layer],
                                                    self.v[layer])
        return out

    def layer_pages(self, li):
        return self.k[li], self.v[li]


def _decode_step_bytes(one_chip, cfg, B, ctx):
    """(device bytes, weight bytes) of one batched paged decode step of
    ``cfg`` compiled for the chip: ``B`` requests of ``ctx`` tokens over a
    full ``POOL_PAGES`` pool.  Weights, pool and temporaries are counted
    once (the pool is donated)."""
    L = len(kv_layer_order(cfg))
    per = ctx // PAGE + 1
    blocks = [list(range(b * per, (b + 1) * per)) for b in range(B)]
    lens, last = [ctx] * B, [1] * B

    def step(params, k, v):
        store = _TracedPages(cfg, k, v)
        logits = paged_decode_step(params, cfg, store, blocks, lens, last,
                                   impl="pallas")
        return logits, store.k, store.v

    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                          abstract_params(cfg))
    pages = [_sds((POOL_PAGES, PAGE, cfg.n_kv_heads * cfg.head_dim_),
                  cfg.dtype, one_chip)] * L
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pages, pages).compile()
    assert compiled.as_text().count("tpu_custom_call") >= L
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    weights = sum(s.size * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    return total, weights


def test_starcoder2_decode_step_fits_one_v5e(one_chip):
    """One batched paged decode step of starcoder2-7b at full width, 16
    layers, 8 requests of 512 tokens each over a 4096-page pool: the
    program compiles for the chip and its weights + pool + temporaries fit
    16 GiB (the pool is donated, so it is not counted twice)."""
    cfg = get_config("starcoder2_7b").scaled(
        groups=dense_stack(DECODE_LAYERS))
    total, weights = _decode_step_bytes(one_chip, cfg, 8, 512)
    assert weights > 7 * GiB                      # really full width
    assert total < 16 * GiB, f"decode step needs {total / GiB:.2f} GiB"


def test_stablelm12_decode_step_leaves_room_on_one_v5e(one_chip):
    """stablelm-12b at full width, 12 layers (the benchmark's cut), a
    32-row decode step at 704 tokens over a 4096-page pool of head dim
    160: under 14.5 GB of the chip's 16.9, which a pool whose 160 lanes
    padded to 256 (6.4 GB of K/V instead of 4.0) could not be."""
    cfg = get_config("stablelm_12b").scaled(
        groups=dense_stack(12, parallel=True))
    total, weights = _decode_step_bytes(one_chip, cfg, 32, 704)
    assert weights > 8 * GiB                      # really full width
    assert total < 14.5e9, f"decode step needs {total / 1e9:.2f} GB"
