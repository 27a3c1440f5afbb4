"""Batched serving loop: continuous-batching-lite over a jitted
prefill + decode_step, with optional TULIP-packed weights.

With packed=True the Engine holds the packed parameter tree *natively*:
every binarizable projection is a PackedArray pytree leaf-bundle
(uint32 words + static layout metadata) that flows straight through
jax.jit into prefill/decode — no unpack-on-load, ~16x less weight HBM
traffic at decode (kernels.packed, DESIGN.md §2–§3).

Requests enter a queue; slots in the fixed decode batch are assigned as
they free up (each slot tracks its own `step`, so sequences of
different lengths coexist in one decode batch — the per-slot position
vector is exactly why decode_step takes step: [B]).

Prefill compiles once per prompt-length *bucket*, not once per request:
prompts are right-padded to the next power of two (clamped to the cache
capacity) and the jitted prefill for that bucket is cached in
`Engine._prefill_cache`, with logits taken at the true last token via
`prefill(lengths=...)`.  Right-padding is safe for attention stacks
(causal masking + the ring-cache invariant: the slot for position p is
rewritten by the real token at decode step p before it is ever
attended to); recurrent stacks (mamba / rglru) carry pad tokens into
their state, so they fall back to exact-length caching — admitting N
same-length requests still traces once.

CPU-runnable: PYTHONPATH=src python -m repro.launch.serve \
    --arch qwen1.5-0.5b --reduced --requests 6 --max-new 8
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.kernels.packed import tree_nbytes
from repro.launch.cache import use_compile_cache
from repro.models import model as M
from repro.models.quantize import pack_model_params
from repro.serving.bucketing import pow2_ceil


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False


class Engine:
    """Fixed-batch decode engine with slot recycling."""

    def __init__(self, cfg, params, batch_slots: int, capacity: int,
                 packed: bool = False, greedy: bool = True):
        self.cfg = cfg
        self.packed = packed
        self.params = pack_model_params(params) if packed else params
        self.param_bytes = tree_nbytes(self.params)
        self.B = batch_slots
        self.capacity = capacity
        self.greedy = greedy
        self.caches = M.init_caches(cfg, batch_slots, capacity)
        self.steps = np.zeros((batch_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self._decode = jax.jit(
            lambda p, b: M.decode_step(p, self.cfg, b))
        self._prefill_cache: Dict[int, Any] = {}
        self.prefill_traces = 0
        # right-padding pads never reach attention output (causal mask +
        # ring-cache overwrite), but they do pollute recurrent state —
        # those archs cache per exact length instead of per bucket
        kinds = set(M.decoder_pattern(cfg))
        self._bucketed = not (kinds & {"mamba", "rglru"}) \
            and not cfg.is_encdec

    def _prefill_len(self, n: int) -> int:
        """Bucket a prompt length: next power of two (the ONE pow2
        rule, shared with the serving engine's batch bucketing in
        repro.serving.bucketing), clamped to the cache capacity
        (padding past capacity would evict real tokens from the ring);
        exact length for recurrent stacks."""
        if not self._bucketed or n >= self.capacity:
            return n
        return min(pow2_ceil(n), self.capacity)

    def _get_prefill(self, padded_len: int):
        """The jitted prefill for one bucketed prompt length — traced
        once, reused for every admit that lands in the bucket."""
        fn = self._prefill_cache.get(padded_len)
        if fn is None:
            def fn(params, tokens, lengths):
                return M.prefill(params, self.cfg, {"tokens": tokens},
                                 cache_capacity=self.capacity,
                                 lengths=lengths)
            fn = jax.jit(fn)
            self._prefill_cache[padded_len] = fn
            self.prefill_traces += 1
        return fn

    def _admit(self, req: Request, slot: int) -> None:
        """Prefill the prompt for one slot and splice its caches in."""
        n = len(req.prompt)
        padded = self._prefill_len(n)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :n] = req.prompt
        logits, caches1 = self._get_prefill(padded)(
            self.params, jnp.asarray(toks), jnp.asarray([n], np.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        req.out.append(tok)
        self.caches = _splice_slot(self.caches, caches1, slot)
        self.steps[slot] = len(req.prompt)
        self.slot_req[slot] = req

    def step(self) -> None:
        toks = np.zeros((self.B, 1), np.int32)
        for s, r in enumerate(self.slot_req):
            if r is not None and r.out:
                toks[s, 0] = r.out[-1]
        batch = {"tokens": jnp.asarray(toks),
                 "step": jnp.asarray(self.steps),
                 "caches": self.caches}
        logits, self.caches = self._decode(self.params, batch)
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            self.steps[s] += 1
            r.out.append(int(nxt[s]))
            if len(r.out) >= r.max_new:
                r.done = True
                self.slot_req[s] = None

    def run(self, requests: List[Request], log=print) -> List[Request]:
        pending = list(requests)

        def active():
            return any(r is not None for r in self.slot_req)

        t0 = time.time()
        n_steps = 0
        while pending or active():
            for s in range(self.B):
                if self.slot_req[s] is None and pending:
                    self._admit(pending.pop(0), s)
            self.step()
            n_steps += 1
        dt = time.time() - t0
        total = sum(len(r.out) for r in requests)
        log(f"served {len(requests)} requests / {total} tokens in "
            f"{n_steps} engine steps, {dt:.2f}s "
            f"({total / max(dt, 1e-9):.1f} tok/s); params "
            f"{self.param_bytes / 1e6:.1f} MB "
            f"({'packed' if self.packed else 'dense'})")
        return requests


def _splice_slot(big_tree, one_tree, slot: int):
    """Write a 1-row prefill cache into slot `slot` of the batch cache.

    The batch axis is 1 for scan-stacked leaves (leading [n_cycles]) and
    0 for remainder-layer leaves — resolved from the tree path."""
    flat_b = jax.tree_util.tree_flatten_with_path(big_tree)
    flat_o, _ = jax.tree_util.tree_flatten(one_tree)
    out = []
    for (path, big), one in zip(flat_b[0], flat_o):
        axis = 1 if any(getattr(k, "key", None) == "layers"
                        for k in path) else 0
        idx = [slice(None)] * big.ndim
        idx[axis] = slice(slot, slot + 1)
        out.append(big.at[tuple(idx)].set(one.astype(big.dtype)))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(big_tree), out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--packed", action="store_true",
                    help="TULIP bit-packed weights")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg).replace(dtype="float32")
    rng = np.random.default_rng(0)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, batch_slots=args.slots,
                 capacity=args.capacity, packed=args.packed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=args.prompt_len).astype(np.int32),
                    args.max_new)
            for i in range(args.requests)]
    eng.run(reqs)
    for r in reqs[:3]:
        print(f"req {r.rid}: +{len(r.out)} tokens {r.out[:8]}")


if __name__ == "__main__":
    main()
