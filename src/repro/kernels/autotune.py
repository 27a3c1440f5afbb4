"""Block-size autotuner for the binarized GEMM kernels.

The kernels do not hard-code their tiles: dispatch routes every kernel
launch through this module, where a cached tuning table keyed on
``(op, backend, M, N, K32)`` returns the block sizes to use, falling
back to a heuristic on a miss (128-row, 128-column, 128-word blocks
clamped to whole TPU tiles) and memoizing it, so repeated shapes hit
the cache.

Entries can come from three places, in priority order:

1. explicit ``put`` calls (e.g. from ``autotune``, which times a set of
   candidate configs through a caller-supplied runner — on a real TPU
   this measures actual kernel wall-time),
2. a JSON table loaded from ``REPRO_TUNING_TABLE`` (or an explicit
   ``load``) — the persisted format is
   ``{"op|backend|M|N|K32": {"bm": int, "bn": int, "bk32": int}, ...}``
   (see DESIGN.md §6 for the contract),
3. the heuristic default.

The table is process-global (like jit's compilation cache): tuning is a
property of the host/backend, not of any one model object.

Inputs/outputs: ``best_blocks`` takes the PADDED problem shape (after
ops.py dispatch applies the BackendSpec padding) and returns a
``BlockConfig(bm, bn, bk32)`` whose members always divide the padded
dims — the kernels re-clamp defensively, but a table hit never forces
a clamp.  Conv launches key through ``best_conv_blocks`` under the
im2col-equivalent GEMM shape (M = HO*WO, N = F_padded, K32 =
KH*KW*C32; DESIGN.md SS7).

Invariants / failure modes:
* fused ``pack_out`` launches use a distinct "<op>+pack" op key — their
  bn tiles output *words* (csa.col_block), so an unfused tuned entry
  (bn tiling columns) must never be served to a fused launch;
* a malformed JSON table raises at ``load`` time (fail fast), while a
  missing ``$REPRO_TUNING_TABLE`` path is silently ignored (tuning is
  an optimization, not a dependency);
* ``autotune`` raises ValueError when no candidate is viable, and its
  first per-config call is discarded as compile time — runners must
  block until ready or every config times as a dispatch.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, Optional, Tuple

from repro.kernels.csa import LANES, SUBLANES, col_block, tile_divisor

ENV_TABLE = "REPRO_TUNING_TABLE"

Key = Tuple[str, str, int, int, int]            # (op, backend, M, N, K32)


@dataclass(frozen=True)
class BlockConfig:
    bm: int
    bn: int
    bk32: int                                   # K blocking in words

    @property
    def bk_bits(self) -> int:
        return 32 * self.bk32

    def to_json(self) -> Dict[str, int]:
        return {"bm": self.bm, "bn": self.bn, "bk32": self.bk32}

    @classmethod
    def from_json(cls, d) -> "BlockConfig":
        return cls(int(d["bm"]), int(d["bn"]), int(d["bk32"]))


def _heuristic(op: str, m: int, n: int, k32: int) -> BlockConfig:
    """128 rows x 128 columns x 128 words, clamped to whole TPU tiles
    (a packed output tiles its words, see csa.col_block).  The CSA
    kernels shrink the row block further (csa.row_block)."""
    return BlockConfig(
        bm=tile_divisor(m, 128, SUBLANES),
        bn=col_block(n, LANES, op.endswith("+pack") and n % 32 == 0),
        bk32=tile_divisor(k32, LANES, LANES))


class TuningTable:
    """shape/backend-keyed block-size cache with JSON persistence."""

    def __init__(self):
        self._entries: Dict[Key, BlockConfig] = {}
        self._loaded_env = False

    @staticmethod
    def _key_str(key: Key) -> str:
        return "|".join(str(p) for p in key)

    @staticmethod
    def _parse_key(s: str) -> Key:
        op, backend, m, n, k32 = s.split("|")
        return (op, backend, int(m), int(n), int(k32))

    def _ensure_env_loaded(self) -> None:
        if self._loaded_env:
            return
        self._loaded_env = True
        path = os.environ.get(ENV_TABLE)
        if path and os.path.exists(path):
            self.load(path)

    def get(self, key: Key) -> Optional[BlockConfig]:
        self._ensure_env_loaded()
        return self._entries.get(key)

    def put(self, key: Key, cfg: BlockConfig) -> BlockConfig:
        self._entries[key] = cfg
        return cfg

    def load(self, path: str) -> None:
        with open(path) as f:
            raw = json.load(f)
        for k, v in raw.items():
            self._entries[self._parse_key(k)] = BlockConfig.from_json(v)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({self._key_str(k): v.to_json()
                       for k, v in sorted(self._entries.items())}, f,
                      indent=1)

    def clear(self) -> None:
        self._entries.clear()


_TABLE = TuningTable()


def get_table() -> TuningTable:
    return _TABLE


def best_blocks(op: str, m: int, n: int, k32: int,
                backend: str = "pallas") -> BlockConfig:
    """Tuned (or heuristic, memoized) block sizes for one GEMM shape.

    op: "popcount_gemm" | "xnor_gemm" | "fused_mlp" | "packed_conv" —
    part of the key because the ops have different VMEM/compute
    balance; fused pack_out launches append "+pack" (their bn tiles
    output words, so tuned entries must not leak across)."""
    key = (op, backend, m, n, k32)
    hit = _TABLE.get(key)
    if hit is not None:
        return hit
    return _TABLE.put(key, _heuristic(op, m, n, k32))


def warm(keys: Iterable[Key]) -> None:
    """Resolve every key through ``best_blocks`` so later dispatches at
    these shapes are guaranteed table hits.  The batch dimension is
    part of every key's M term — the serving engine calls this once per
    batch *bucket* (keys from ``CompiledBNN.tuning_keys_for_batch``),
    which is the one place a new M enters the table outside dispatch."""
    for op, backend, m, n, k32 in keys:
        best_blocks(op, m, n, k32, backend)


def best_conv_blocks(op: str, ho: int, wo: int, f: int, k32: int,
                     backend: str = "pallas") -> BlockConfig:
    """Conv launches share the GEMM tuning table under the im2col-
    equivalent key: a [N, HO, WO, C] conv with KH x KW filters is the
    GEMM  M = HO*WO (rows per resident image), N = F (padded), K32 =
    KH*KW*C32 (tap-major filter words) — see DESIGN.md SS7.  Only the
    direct kernel (kernels/packed_conv.py) consumes these entries (it
    blocks the F axis with ``bn``); the im2col fallback goes through
    ops.binary_binary_dense and is tuned under its own
    "popcount_gemm[+pack]" keys with the flattened patch-matrix shape.
    op: "packed_conv" or "packed_conv+pack"."""
    return best_blocks(op, ho * wo, f, k32, backend)


def candidate_blocks(m: int, n: int, k32: int) -> Iterable[BlockConfig]:
    """Sensible sweep for ``autotune``: power-of-two tiles clamped to
    whole-tile divisors, deduplicated."""
    seen = set()
    for bm in (256, 128, 64, 32, 8):
        for bn in (512, 256, 128):
            for bk in (512, 256, 128):
                cfg = BlockConfig(bm=tile_divisor(m, bm, SUBLANES),
                                  bn=tile_divisor(n, bn, LANES),
                                  bk32=tile_divisor(k32, bk, LANES))
                if cfg not in seen:
                    seen.add(cfg)
                    yield cfg


def autotune(op: str, m: int, n: int, k32: int, backend: str,
             runner: Callable[[BlockConfig], None],
             candidates: Optional[Iterable[BlockConfig]] = None,
             iters: int = 3) -> BlockConfig:
    """Time ``runner(cfg)`` (which must block until ready) over the
    candidate configs, store the winner in the table, and return it.
    The first call per config is discarded as compile time."""
    best: Optional[Tuple[float, BlockConfig]] = None
    for cfg in (candidates if candidates is not None
                else candidate_blocks(m, n, k32)):
        runner(cfg)                        # compile / warm-up
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            runner(cfg)
            ts.append(time.perf_counter() - t0)
        t = min(ts)
        if best is None or t < best[0]:
            best = (t, cfg)
    if best is None:
        raise ValueError("no viable block candidates for "
                         f"{op} {m}x{n}x{k32}")
    return _TABLE.put((op, backend, m, n, k32), best[1])
