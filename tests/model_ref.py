"""The reference forward the paged serving path is held to:
:func:`repro.models.model.apply_model`, run without pages, schedulers or
kernels.  Test helpers only."""

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import apply_model, init_cache
from repro.runtime.kv_store import kv_layer_order


def prefill_kv(params, cfg,
               tokens: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """A prompt's per-layer post-rope K/V from the full-sequence prefill
    forward, as ``(L, S, Hkv, hd)`` numpy arrays in :func:`kv_layer_order`
    order -- ready for ``PagedKVStore.write_prefill``."""
    toks = jnp.asarray([list(tokens)], jnp.int32)
    _, cache, _ = apply_model(params, toks, cfg=cfg, mode="prefill")
    ks, vs = [], []
    for gi, pi, rep in kv_layer_order(cfg):
        lc = cache["groups"][f"g{gi}"][f"p{pi}"]
        # the cache's own dtype: the pages hold exactly these values
        ks.append(np.asarray(lc["k"][rep, 0]))               # (S, Hkv, hd)
        vs.append(np.asarray(lc["v"][rep, 0]))
    return np.stack(ks), np.stack(vs)


class GreedyReference:
    """Greedy tokens of ``apply_model`` in decode mode against a
    per-request cache, one token at a time: the prompt, then its last
    token fed again (as the serving path feeds it), then each chosen
    token.  Memoized per prompt."""

    def __init__(self, params, cfg, max_new: int, max_seq: int = 64):
        self.params, self.cfg = params, cfg
        self.max_new, self.max_seq = max_new, max_seq
        self._decode = jax.jit(lambda p, c, t: apply_model(
            p, t, cfg=cfg, mode="decode", cache=c))
        self._memo = {}

    def __call__(self, prompt: Sequence[int]) -> List[int]:
        prompt = tuple(prompt)
        if prompt not in self._memo:
            cache = init_cache(self.cfg, 1, self.max_seq, self.cfg.dtype)
            toks = jnp.asarray([list(prompt)], jnp.int32)
            for t in range(len(prompt)):
                _, cache, _ = self._decode(self.params, cache,
                                           toks[:, t:t + 1])
            out, last = [], prompt[-1]
            for _ in range(self.max_new):
                logits, cache, _ = self._decode(
                    self.params, cache, jnp.asarray([[last]], jnp.int32))
                last = int(jnp.argmax(logits[0, -1]))
                out.append(last)
            self._memo[prompt] = out
        return list(self._memo[prompt])
