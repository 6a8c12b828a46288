"""Serving: the forest inference server + the LM prefill/decode engine.

`ForestServer` is the ROADMAP "serving export path" wire-up: a long-lived
process loads ONE versioned `PackedForest` .npz (`forest.PackedForest.save`)
and serves `predict` off the stacked arrays — the jitted whole-forest
descent is compiled ONCE at `load` time by a warm-up call, so the first
real request pays no trace.  `benchmarks/run.py serve` records the p50
single-row latency of exactly this path.

The LM half (prefill + decode steps and a batched request engine) keeps
two KV-cache sharding recipes (DESIGN.md §5):
  * "batch"  — batch over "data", kv-heads over "model" (decode_32k, B=128)
  * "seq"    — cache sequence over "data" (flash-decoding-style partial
               softmax combine left to XLA SPMD), heads over "model"
               (long_500k, B=1)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import transformer
from repro.train import sharding as shd


# ---------------------------------------------------------------------------
# Forest serving (ROADMAP "Serving export path" follow-up)
# ---------------------------------------------------------------------------

class InvalidRequest(ValueError):
    """A malformed predict request (DESIGN.md §9 graceful degradation).

    Raised by `ForestServer.predict` BEFORE the jitted descent for
    wrong-shape inputs, non-finite numeric rows, or categorical ids
    outside the declared arity — the cases that would otherwise either
    crash out of the serving loop or silently route every row down a
    garbage path.  The server holds no per-request state, so catching
    this and answering the client with an error leaves it serving."""

@dataclasses.dataclass
class ForestServer:
    """Low-latency inference server over an exported `PackedForest`.

    Usage:
        srv = ForestServer.load("model.npz")    # load + warm the jit
        probs = srv.predict(num_row, cat_row)   # (B, C), no first-call jit

    `load` deserializes the versioned .npz (no pickle, no training code)
    and immediately runs one dummy batch through `predict_proba` per
    common batch size so the descent program is compiled before traffic
    arrives.  Single-row latency is the serving-critical number
    (`benchmarks/run.py serve` measures its p50 on this exact class).
    """

    packed: object                      # forest.PackedForest
    m_cat: int = 0
    arities: Optional[tuple] = None     # per categorical column, if known

    @classmethod
    def load(cls, path, m_cat: int = 0,
             warm_batch_sizes=(1,), arities=None) -> "ForestServer":
        """Load an exported forest and pre-compile the descent.

        `m_cat` is the categorical input width requests will carry (the
        .npz stores only the model; 0 for all-numeric forests).
        `warm_batch_sizes` picks which request shapes are traced at
        startup (the descent retraces per batch size — warm every size
        the service will see; 1 covers the single-row latency path).
        `arities` (optional, len m_cat) enables per-column range checks
        on categorical ids: an out-of-arity id raises `InvalidRequest`
        instead of indexing the split mask at a wrong row.
        """
        from repro.core.forest import PackedForest
        packed = PackedForest.load(path)
        if arities is not None:
            arities = tuple(int(a) for a in arities)
            if len(arities) != int(m_cat):
                raise ValueError(
                    f"arities has {len(arities)} entries but m_cat="
                    f"{int(m_cat)} — pass one arity per categorical "
                    f"column")
        srv = cls(packed=packed, m_cat=int(m_cat), arities=arities)
        if srv._needs_cat() and srv.m_cat == 0:
            raise ValueError(
                "this forest splits on categorical features but the "
                "server was loaded with m_cat=0 — pass the dataset's "
                "categorical column count to ForestServer.load(path, "
                "m_cat=...) so requests carry the categorical row")
        for b in warm_batch_sizes:
            num = jnp.zeros((b, packed.m_num), jnp.float32)
            cat = jnp.zeros((b, srv.m_cat), jnp.int32)
            jax.block_until_ready(packed.predict_proba(num, cat))
        return srv

    def _needs_cat(self) -> bool:
        return bool(np.asarray(self.packed.is_cat).any())

    def _validate(self, num: np.ndarray, cat) -> np.ndarray:
        """Reject malformed requests with `InvalidRequest` (typed, safe
        to catch-and-answer) before anything reaches the device."""
        if num.ndim != 2 or num.shape[1] != self.packed.m_num:
            raise InvalidRequest(
                f"numeric input must be (B, {self.packed.m_num}), got "
                f"shape {tuple(num.shape)}")
        if num.size and not np.isfinite(num).all():
            bad = np.argwhere(~np.isfinite(num))[0]
            raise InvalidRequest(
                f"numeric input contains a non-finite value at row "
                f"{int(bad[0])}, column {int(bad[1])} — NaN/inf would "
                f"route every comparison to the right child silently")
        if cat is None:
            if self.m_cat:
                raise InvalidRequest(
                    f"this server was loaded with m_cat={self.m_cat}: "
                    "every request must carry a (B, m_cat) categorical "
                    "array (an empty one would silently route every "
                    "categorical split by category 0)")
            return np.zeros((num.shape[0], 0), np.int32)
        cat = np.asarray(cat)
        if not np.issubdtype(cat.dtype, np.integer):
            raise InvalidRequest(
                f"categorical input must be integer ids, got dtype "
                f"{cat.dtype}")
        if cat.ndim != 2 or cat.shape[1] != self.m_cat:
            raise InvalidRequest(
                f"categorical input must be (B, {self.m_cat}), got "
                f"shape {tuple(cat.shape)}")
        if cat.shape != (num.shape[0], self.m_cat):
            raise InvalidRequest(
                f"categorical batch {cat.shape[0]} != numeric batch "
                f"{num.shape[0]}")
        if cat.size:
            if cat.min() < 0:
                raise InvalidRequest("categorical ids must be >= 0")
            if self.arities is not None:
                hi = cat.max(axis=0)
                for j, a in enumerate(self.arities):
                    if int(hi[j]) >= a:
                        raise InvalidRequest(
                            f"categorical column {j} has id "
                            f"{int(hi[j])} but arity {a} (valid ids "
                            f"0..{a - 1})")
        return cat.astype(np.int32, copy=False)

    def predict(self, num, cat=None) -> np.ndarray:
        """(B, C) forest-mean distributions on the host; ONE jitted call.

        Malformed requests raise `InvalidRequest` before the descent —
        the caller answers the client and keeps serving (no state to
        recover; see tests/test_server_robust.py).  Its phases are the
        spans `repro.serve.{validate,transfer,descent,fetch}`."""
        with obs.span("repro.serve.validate"):
            num = np.asarray(num, np.float32)
            cat = self._validate(num, cat)
        with obs.span("repro.serve.transfer"):
            num_d, cat_d = jnp.asarray(num), jnp.asarray(cat, jnp.int32)
        with obs.span("repro.serve.descent"):
            out = self.packed.predict_proba(num_d, cat_d)
        with obs.span("repro.serve.fetch"):
            return np.asarray(out)


def prefill_step(params, inputs, cfg, unroll: bool = False):
    """Full-sequence forward; returns (last-position logits, layer caches).

    Only the final position's logits are projected — materializing the full
    (B, S, vocab) tensor at 32k prefill would be pure waste (the sampler
    consumes one position).
    """
    x, _, caches = transformer.forward_hidden(params, inputs, cfg,
                                              collect_cache=True,
                                              unroll=unroll)
    logits = transformer.project_logits(params, x[:, -1:], cfg)
    return logits, caches


def decode_step(params, caches, inputs, cache_len, cfg, unroll: bool = False):
    """One new token against a max_seq cache (the dry-run decode workload)."""
    return transformer.decode_step(params, caches, inputs, cache_len, cfg,
                                   unroll=unroll)


def greedy_sample(logits):
    return jnp.argmax(logits[:, -1], axis=-1)


@dataclasses.dataclass
class BatchedServer:
    """Minimal batched continuous-decode server for the examples.

    Holds a fixed-size batch of slots; each slot has a cache position.  New
    requests prefill into a free slot; every `step()` decodes one token for
    all active slots.
    """
    cfg: object
    params: object
    max_seq: int
    batch: int

    def __post_init__(self):
        self.caches = transformer.init_cache(self.cfg, self.batch, self.max_seq)
        self.lens = jnp.zeros((self.batch,), jnp.int32)
        self.active = [False] * self.batch
        self.outputs: list[list[int]] = [[] for _ in range(self.batch)]
        self._decode = jax.jit(
            lambda p, c, t, l: transformer.decode_step(p, c, t, l, self.cfg))

    def add_request(self, prompt_tokens) -> int:
        slot = self.active.index(False)
        toks = jnp.asarray(prompt_tokens, jnp.int32)
        # sequential prefill through decode steps (simple, exercises the
        # same path; bulk prefill_step is used by examples/serve_lm.py)
        for t in toks:
            tok = jnp.zeros((self.batch, 1), jnp.int32).at[slot, 0].set(t)
            _, self.caches = self._decode(self.params, self.caches, tok, self.lens)
            self.lens = self.lens.at[slot].add(1)
        self.active[slot] = True
        return slot

    def step(self) -> dict[int, int]:
        """Decode one token for every active slot; returns {slot: token}."""
        last = jnp.asarray(
            [self.outputs[i][-1] if self.outputs[i] else 0
             for i in range(self.batch)], jnp.int32)[:, None]
        logits, self.caches = self._decode(self.params, self.caches, last, self.lens)
        nxt = jnp.argmax(logits[:, 0], axis=-1)
        out = {}
        for i in range(self.batch):
            if self.active[i]:
                tok = int(nxt[i])
                self.outputs[i].append(tok)
                self.lens = self.lens.at[i].add(1)
                out[i] = tok
        return out

    def finish(self, slot: int) -> list[int]:
        self.active[slot] = False
        toks, self.outputs[slot] = self.outputs[slot], []
        self.lens = self.lens.at[slot].set(0)
        return toks
