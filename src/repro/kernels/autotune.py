"""Backend-aware batch-tile autotuning for the kernel entry points.

Every kernel wrapper takes a ``tile`` — the batch-tile edge of its
(prime, batch_tile) Pallas grid.  The historical default was a fixed 8
regardless of backend, ring size, or batch; this module picks it
per ``(backend, kernel family, k, n, b, dtype)`` instead.  The dtype
component keeps scheme families apart: a uint16 small-ring workload
(ML-KEM's n=256/q=3329) must never collide with the uint32 CKKS entry
for the same (family, k, n, b) — their kernels, lane widths and best
tiles are unrelated.

Resolution order (``resolve_tile``) — NOTHING here ever measures
implicitly, so jit-signature counts stay bounded and the PR 6
``fresh_traces`` discipline survives:

  1. an explicit ``tile=`` argument (clamped to the batch),
  2. the ``SCE_NTT_TILE`` env pin (CI sets this for determinism),
  3. a cached result (in-process, seeded from the optional on-disk
     JSON named by ``SCE_NTT_AUTOTUNE_CACHE``),
  4. a fresh measurement — ONLY when ``SCE_NTT_AUTOTUNE=1``, the family
     has a registered runner, and we are outside any jit trace,
  5. the static default ``min(8, b)``.

Every path clamps to a tile the TPU accepts (``clamp``): the tile is the
second-minor block dim of every kernel, so it is a multiple of 8 or the
whole batch.  A batch of at most 8 rows runs as one whole-batch tile (a
1-row input is never zero-padded to an 8-row dispatch); a wider batch
takes a multiple of 8 no wider than the request, and the entry points
pad the batch to a tile multiple.

Sharded dispatch resolves against the PER-SHARD batch: a caller whose
(B, k, n) stack is split over ``shards`` mesh devices passes
``shards=`` and every step of the funnel — cache key, clamp, measured
workload — sees ``ceil(b / shards)`` instead of the global ``b``.  A
mesh of 4 devices over b=32 therefore hits (and writes) the b=8 cache
entry: the kernel grid each device actually runs is 8 rows wide, and
keying on the global batch would tune (and cache) tiles for a shape no
device ever dispatches.  Tile resolution INSIDE a ``shard_map`` body
needs no ``shards=`` — the entry points see the local block shape
there, which is already the per-shard batch.

Benchmarks that want a tuned tile regardless of the env flag call
``ensure(family, k, n, b)``, which measures on a cache miss (still
honoring the pin first).  ``table()`` / ``dump(path)`` snapshot the
cache for the CI artifact next to ``BENCH_smoke.json``.
"""
from __future__ import annotations

import functools
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs

# Legal batch tiles (multiples of the 8-row sublane tile).  32 bounds
# the widest elementwise block: dyadic_inner_banks at d=8 digits holds
# ~8.5 MiB of double-buffered VMEM at tile 32 (ntt_kernel.LANE_BLOCK).
CANDIDATE_TILES = (8, 16, 32)
DEFAULT_TILE = 8
SUBLANES = 8

ENV_PIN = "SCE_NTT_TILE"
ENV_CACHE = "SCE_NTT_AUTOTUNE_CACHE"
ENV_AUTOTUNE = "SCE_NTT_AUTOTUNE"

# (backend, family, k, n, b, dtype) -> best tile
_MEM: dict[tuple, int] = {}
# (same key) -> measurement evidence: {"chosen": tile, "source": how the
# entry came to be ("measured" / "default-nonu32" / "disk"),
# "candidates": {tile: median seconds}} — the tuner used to throw its
# measurements away the moment the argmin was taken, so a surprising
# cached tile could never be audited; now the full candidate table
# rides the metrics registry and the SCE_NTT_AUTOTUNE_CACHE sidecar
_EVIDENCE: dict[tuple, dict] = {}
_DISK_LOADED = False
_KEY_PARTS = 6      # the persisted "be|fam|k|n|b|dtype" format


def clamp(tile: int, b: int) -> int:
    """The universal tile rule, legal on TPU: the whole batch when the
    request covers it (or the batch is at most one sublane tile), else
    the largest multiple of 8 not above the request (at least 8)."""
    b = int(b)
    if b <= 0:
        return 1
    t = max(SUBLANES, int(tile) // SUBLANES * SUBLANES)
    return b if t >= b else t


def _backend() -> str:
    return jax.default_backend()


def _key(family: str, k: int, n: int, b: int,
         dtype: str = "uint32") -> tuple:
    return (_backend(), family, int(k), int(n), int(b), str(dtype))


def _trace_clean() -> bool:
    """True only when called outside any jit trace — measuring inside a
    trace would time tracing, not compute, and could poison the cache.
    Under a trace every jax op is staged out, so a fresh iota comes back
    as a ``jax.core.Tracer``."""
    return not isinstance(lax.iota(jnp.int32, 1), jax.core.Tracer)


def _env_pin() -> int | None:
    v = os.environ.get(ENV_PIN)
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        return None


def _load_disk() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    path = os.environ.get(ENV_CACHE)
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            data = json.load(f)
        stale = 0
        evidence = data.get("evidence", {})
        for ks, tile in data.get("entries", {}).items():
            parts = ks.split("|")
            if len(parts) == _KEY_PARTS:
                be, fam, k, n, b, dt = parts
                key = (be, fam, int(k), int(n), int(b), dt)
                _MEM[key] = int(tile)
                # provenance survives the round trip: a disk-seeded
                # entry keeps its measured candidate table (if the
                # sidecar carried one) but is marked as coming from disk
                ev = evidence.get(ks, {})
                _EVIDENCE[key] = {
                    "chosen": int(tile), "source": "disk",
                    "candidates": {int(t): float(s) for t, s in
                                   ev.get("candidates", {}).items()}}
            else:
                # pre-dtype (5-part) entries are ambiguous: silently
                # reading one as uint32 could hand a u16 family a tile
                # tuned for the wrong lane width — skip them loudly
                stale += 1
        if stale:
            warnings.warn(
                f"autotune: ignoring {stale} old-format entr"
                f"{'y' if stale == 1 else 'ies'} in {path!r} (expected "
                f"{_KEY_PARTS}-part 'backend|family|k|n|b|dtype' keys); "
                "re-measure to refresh the cache", stacklevel=2)
    except (OSError, ValueError, KeyError):
        pass    # a stale/corrupt cache must never break dispatch


def _save_disk() -> None:
    path = os.environ.get(ENV_CACHE)
    if not path:
        return
    try:
        with open(path, "w") as f:
            json.dump(table(), f, indent=1, sort_keys=True)
    except OSError:
        pass


def table() -> dict:
    """JSON-ready snapshot of the tuning state (the CI artifact).

    ``entries`` keeps the stable "key -> tile" mapping older sidecars
    round-trip on; ``evidence`` adds the measurement provenance per
    entry (chosen tile, how it was chosen, and the full candidate
    tile -> median-seconds table when a measurement ran)."""
    return {
        "backend": _backend(),
        "pin": _env_pin(),
        "entries": {
            "|".join(str(p) for p in key): tile
            for key, tile in sorted(_MEM.items())
        },
        "evidence": {
            "|".join(str(p) for p in key): {
                "chosen": ev.get("chosen"),
                "source": ev.get("source"),
                "candidates": {str(t): s for t, s in
                               sorted(ev.get("candidates", {}).items())},
            }
            for key, ev in sorted(_EVIDENCE.items())
        },
    }


def dump(path: str) -> None:
    with open(path, "w") as f:
        json.dump(table(), f, indent=1, sort_keys=True)


def clear() -> None:
    """Drop the in-process cache (tests)."""
    global _DISK_LOADED
    _MEM.clear()
    _EVIDENCE.clear()
    _DISK_LOADED = True     # don't resurrect entries from disk


def shard_batch(b: int, shards: int = 1) -> int:
    """The per-shard batch a ``shards``-way data-parallel dispatch hands
    each device: ``ceil(b / shards)`` (the last shard may run padded)."""
    b, shards = int(b), max(1, int(shards))
    return -(-b // shards) if b > 0 else b


def resolve_tile(family: str, k: int, n: int, b: int,
                 tile: int | None = None, *, shards: int = 1,
                 dtype: str = "uint32") -> int:
    """The one tile-resolution funnel every entry point routes through.

    ``shards`` > 1 resolves against the per-shard batch ``ceil(b /
    shards)`` — the batch each mesh device actually dispatches — so the
    cache key, the clamp and any measurement all describe the kernel
    grid that really runs (see module docstring).  ``dtype`` is the ring
    element dtype name; non-u32 families resolve through their own cache
    entries and never alias the CKKS u32 ones."""
    b = shard_batch(b, shards)
    if tile is not None:
        obs.counter_add("autotune.resolve.explicit")
        return clamp(tile, b)
    pin = _env_pin()
    if pin is not None:
        obs.counter_add("autotune.resolve.pin")
        return clamp(pin, b)
    _load_disk()
    key = _key(family, k, n, b, dtype)
    hit = _MEM.get(key)
    if hit is not None:
        obs.counter_add("autotune.resolve.cache_hit")
        return clamp(hit, b)
    obs.counter_add("autotune.resolve.cache_miss")
    if (os.environ.get(ENV_AUTOTUNE) == "1" and family in _RUNNERS
            and _trace_clean()):
        return clamp(measure(family, k, n, b, dtype=dtype), b)
    obs.counter_add("autotune.resolve.default")
    return clamp(DEFAULT_TILE, b)


def ensure(family: str, k: int, n: int, b: int, *, shards: int = 1,
           dtype: str = "uint32") -> int:
    """Measure-on-miss (benchmarks): pin > cache > measure > default.
    ``shards`` resolves against the per-shard batch like ``resolve_tile``."""
    b = shard_batch(b, shards)
    pin = _env_pin()
    if pin is not None:
        obs.counter_add("autotune.resolve.pin")
        return clamp(pin, b)
    _load_disk()
    key = _key(family, k, n, b, dtype)
    hit = _MEM.get(key)
    if hit is not None:
        obs.counter_add("autotune.resolve.cache_hit")
        return clamp(hit, b)
    obs.counter_add("autotune.resolve.cache_miss")
    if family in _RUNNERS and _trace_clean():
        return clamp(measure(family, k, n, b, dtype=dtype), b)
    obs.counter_add("autotune.resolve.default")
    return clamp(DEFAULT_TILE, b)


def measure(family: str, k: int, n: int, b: int, *, reps: int = 3,
            dtype: str = "uint32") -> int:
    """Time every legal candidate tile for the family's representative
    workload and cache the argmin.  Only legal tiles (``clamp``) are
    tried, so a tile that fails to compile or run is a bug and raises.
    The registered runners are u32 workloads; a non-u32 dtype caches
    the static default until a same-width runner exists (never a tile
    timed on the wrong lane width)."""
    key = _key(family, k, n, b, dtype)
    if dtype != "uint32":
        _MEM[key] = clamp(DEFAULT_TILE, b)
        _EVIDENCE[key] = {"chosen": _MEM[key], "source": "default-nonu32",
                          "candidates": {}}
        _save_disk()
        return _MEM[key]
    run = _RUNNERS[family](int(k), int(n), int(b))
    cands = sorted({clamp(t, b) for t in CANDIDATE_TILES})
    best_tile, best_t = clamp(DEFAULT_TILE, b), float("inf")
    candidates: dict[int, float] = {}
    with obs.span("autotune.measure", family=family, k=int(k), n=int(n),
                  b=int(b), dtype=dtype):
        for t in cands:
            jax.block_until_ready(run(t))           # compile + warm
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(run(t))
                times.append(time.perf_counter() - t0)
            dt = min(times)
            # selection stays argmin-of-min (noise-floor tiles win);
            # the median is the honest per-candidate summary recorded
            # as evidence (min overstates a lucky pass)
            candidates[t] = float(sorted(times)[len(times) // 2])
            if dt < best_t:
                best_tile, best_t = t, dt
    _MEM[key] = best_tile
    _EVIDENCE[key] = {"chosen": best_tile, "source": "measured",
                      "candidates": candidates}
    if obs.enabled():
        obs.counter_add("autotune.measurements")
        keystr = "|".join(str(p) for p in key)
        for t, s in candidates.items():
            obs.gauge_set(f"autotune.candidate_s.{keystr}.tile{t}", s)
        obs.gauge_set(f"autotune.chosen.{keystr}", best_tile)
    _save_disk()
    return best_tile


# -------------------------------------------------- family runners
#
# Each runner builds a representative synthetic workload ONCE (cached)
# and returns ``fn(tile) -> array`` for the timer.  ops is imported
# lazily: autotune must stay importable from ops without a cycle.

@functools.lru_cache(maxsize=8)
def _params(n: int):
    from repro.core.params import make_ntt_params
    return make_ntt_params(n)


@functools.lru_cache(maxsize=8)
def _pack(k: int, n: int):
    from repro.core.params import gen_ntt_primes
    from repro.fhe.batched import build_table_pack
    return build_table_pack(gen_ntt_primes(k, n), n)


def _rng_rows(shape, q):
    rng = np.random.default_rng(0xC0FFEE)
    return rng.integers(0, int(q), size=shape, dtype=np.uint32)


def _run_ntt(k, n, b, inverse=False):
    from repro.kernels import ops
    p = _params(n)
    x = _rng_rows((b, n), p.q)
    fn = ops.intt if inverse else ops.ntt
    return lambda tile: fn(x, p, use_pallas=True, tile=tile)


def _run_ntt_banks(k, n, b, inverse=False):
    from repro.kernels import ops
    t = _pack(k, n)
    x = np.stack([_rng_rows((b, n), q) for q in np.asarray(t["qs"])])
    fn = ops.intt_banks if inverse else ops.ntt_banks
    return lambda tile: fn(x, t, use_pallas=True, tile=tile)


def _run_dyadic(k, n, b, mac=False):
    from repro.kernels import ops
    p = _params(n)
    a = _rng_rows((b, n), p.q)
    c = _rng_rows((b, n), p.q)
    if mac:
        acc = _rng_rows((b, n), p.q)
        return lambda tile: ops.dyadic_mac(acc, a, c, p, use_pallas=True,
                                           tile=tile)
    return lambda tile: ops.dyadic_mul(a, c, p, use_pallas=True, tile=tile)


def _run_dyadic_inner_banks(k, n, b):
    from repro.kernels import ops
    t = _pack(k, n)
    d = 2
    qs = np.asarray(t["qs"])
    ext = np.stack([np.stack([_rng_rows((b, n), q) for q in qs])
                    for _ in range(d)])
    evk = np.stack([np.stack([_rng_rows((n,), q) for q in qs])
                    for _ in range(d)])
    return lambda tile: ops.dyadic_inner_banks(ext, evk, t, use_pallas=True,
                                               tile=tile)


_RUNNERS = {
    "ntt": _run_ntt,
    "intt": functools.partial(_run_ntt, inverse=True),
    "dyadic_mul": _run_dyadic,
    "dyadic_mac": functools.partial(_run_dyadic, mac=True),
    "ntt_banks": _run_ntt_banks,
    "intt_banks": functools.partial(_run_ntt_banks, inverse=True),
    "dyadic_inner_banks": _run_dyadic_inner_banks,
    "serve_batch": _run_ntt_banks,              # batch-shaped proxy
}
