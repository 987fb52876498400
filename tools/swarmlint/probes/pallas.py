"""``pallas-grid`` — every Pallas kernel's block shapes must divide the
geometry of every config the engine can serve, and tile it the way the
TPU's compiler accepts.

The kernels tile with ``grid = dim // block``; a block that does not
divide its dimension trips the kernel's divisibility assert on TPU at
the first request with that geometry — long after CI's interpret-mode
parity tests passed on friendlier shapes.  A block whose sublane size is
not a multiple of 8 (lane size: 128) and not the whole dim is refused by
the TPU compiler outright.  This probe sweeps, for every config in
``configs/`` (full *and* smoke):

* the decode-attention time tile over every legal cache length (the
  engine grows caches in 64-slot granules, lcm'd with attn_kv_block
  beyond one block);
* the flash-attention (bq, bk) tiles over prompt buckets x cache
  lengths;
* the uncertainty kernel's (bn, bv) tiles over the config's vocabulary
  and serving batch sizes;
* the paged ring constraint: a windowed config's window must be a
  multiple of the pool block length (the ring view is whole blocks).
"""
from __future__ import annotations

import math
from typing import List

from ..report import Finding

PROBE_ID = "pallas-grid"

_BLOCKING_PATH = "src/repro/kernels/blocking.py"
_ENGINE_PATH = "src/repro/serving/engine.py"

# engine geometry: caches grow in 64-slot granules; serve batches are
# small powers of two; probe sweeps beyond the defaults for headroom
_MAX_CACHE_LEN = 4096
_BATCHES = (1, 2, 3, 4, 8, 16)


def _cache_lengths(kv_block: int, block_len: int) -> List[int]:
    """Legal cache lengths: multiples of 64 up to one kv block, then
    multiples of lcm(kv_block, block_len) (mirrors engine._cache_len)."""
    lengths = [n for n in range(64, _MAX_CACHE_LEN + 1, 64)]
    g = math.lcm(kv_block, block_len)
    lengths += [n for n in range(g, 4 * g + 1, g)]
    return sorted(set(lengths))


def _tiles(dim: int, block: int, align: int) -> bool:
    """``block`` divides ``dim`` and is a legal TPU block dim: a multiple
    of ``align`` or the whole ``dim``."""
    return dim % block == 0 and (block % align == 0 or block == dim)


def check() -> List[Finding]:
    from repro import configs as C
    from repro.kernels import blocking
    from repro.serving import engine as E

    import dataclasses
    block_len = next(f.default for f in dataclasses.fields(E.InferenceEngine)
                     if f.name == "block_len")

    findings: List[Finding] = []
    seen = set()

    def bad(path: str, msg: str) -> None:
        if msg in seen:
            return
        seen.add(msg)
        findings.append(Finding(PROBE_ID, path, 0, msg))

    for arch in C.ARCH_IDS:
        for cfg, is_full in ((C.get_config(arch), True),
                             (C.get_smoke(arch), False)):
            kvb = cfg.attn_kv_block
            sub, lane = blocking.SUBLANE, blocking.LANE
            for T in _cache_lengths(kvb, block_len):
                bt = blocking.decode_blocks(T)
                if not _tiles(T, bt, sub):
                    bad(_BLOCKING_PATH,
                        f"{arch}: decode tile {bt} does not tile cache "
                        f"length {T}")
                for S in (8, 64, 128, 256, 320, 512, 1024):
                    bq, bk = blocking.flash_blocks(S, T)
                    if not (_tiles(S, bq, sub) and _tiles(T, bk, sub)):
                        bad(_BLOCKING_PATH,
                            f"{arch}: flash tiles ({bq}, {bk}) do not "
                            f"tile (S={S}, T={T})")
            V = cfg.vocab_size
            for N in _BATCHES:
                bn, bv = blocking.uncertainty_blocks(N, V)
                if not (_tiles(N, bn, sub) and _tiles(V, bv, lane)):
                    bad(_BLOCKING_PATH,
                        f"{arch}: uncertainty tiles ({bn}, {bv}) do not "
                        f"tile (N={N}, V={V})")
            if block_len % sub:
                bad(_ENGINE_PATH,
                    f"pool block_len {block_len} is not a multiple of "
                    f"{sub}: the decode kernel DMAs whole blocks as its "
                    "sublane tile")
            # smoke configs pick a matching block_len at construction (the
            # engine validates); the DEFAULT block_len must fit full configs
            if is_full and cfg.window is not None and \
                    cfg.window % block_len:
                bad(_ENGINE_PATH,
                    f"{arch}: local-attention window {cfg.window} is not "
                    f"a multiple of pool block_len {block_len}; the paged "
                    "ring view cannot cover it with whole blocks")
    return findings
