"""Tile-resolution rules for ``kernels.autotune``.

The invariants under test: explicit > pin > cache > (gated) measure >
default; every path clamps to the batch; nothing measures implicitly
(no env flag, or inside a jit trace) so jit-signature counts and the
serve-path ``fresh_traces`` discipline stay intact.
"""
import json

import jax
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.core.params import make_ntt_params


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(autotune.ENV_PIN, raising=False)
    monkeypatch.delenv(autotune.ENV_CACHE, raising=False)
    monkeypatch.delenv(autotune.ENV_AUTOTUNE, raising=False)
    autotune.clear()
    yield
    autotune.clear()


def test_clamp_rule():
    """Every clamped tile is legal on TPU: the whole batch, or a
    multiple of 8 no wider than the request."""
    assert autotune.clamp(8, 1) == 1
    assert autotune.clamp(8, 5) == 5
    assert autotune.clamp(8, 100) == 8
    assert autotune.clamp(0, 4) == 4          # sub-sublane batch: whole
    assert autotune.clamp(4, 100) == 8        # below one sublane tile
    assert autotune.clamp(20, 100) == 16      # rounded down to 8k
    assert autotune.clamp(64, 40) == 40       # request covers the batch
    assert autotune.clamp(8, 0) == 1
    assert autotune.clamp(8, -3) == 1
    for b in range(1, 70):
        for t in (1, 2, 4, 8, 12, 16, 32, 64):
            c = autotune.clamp(t, b)
            assert c == b or (c % 8 == 0 and c < b), (t, b, c)


def test_resolve_precedence():
    # default: min(8, b)
    assert autotune.resolve_tile("ntt", 1, 256, 100) == autotune.DEFAULT_TILE
    assert autotune.resolve_tile("ntt", 1, 256, 3) == 3
    # explicit beats everything, still clamped
    assert autotune.resolve_tile("ntt", 1, 256, 5, tile=32) == 5
    assert autotune.resolve_tile("ntt", 1, 256, 100, tile=16) == 16


def test_env_pin(monkeypatch):
    monkeypatch.setenv(autotune.ENV_PIN, "32")
    assert autotune.resolve_tile("ntt", 1, 256, 100) == 32
    assert autotune.resolve_tile("ntt", 1, 256, 2) == 2    # still clamped
    # explicit argument outranks the pin
    assert autotune.resolve_tile("ntt", 1, 256, 100, tile=16) == 16
    # garbage pin falls through to the default, never raises
    monkeypatch.setenv(autotune.ENV_PIN, "banana")
    assert autotune.resolve_tile("ntt", 1, 256, 100) == autotune.DEFAULT_TILE


def test_cache_hit_beats_default(monkeypatch):
    key = (jax.default_backend(), "ntt", 1, 256, 100, "uint32")
    monkeypatch.setitem(autotune._MEM, key, 16)
    assert autotune.resolve_tile("ntt", 1, 256, 100) == 16
    # pin still outranks the cache
    monkeypatch.setenv(autotune.ENV_PIN, "32")
    assert autotune.resolve_tile("ntt", 1, 256, 100) == 32


def test_no_measurement_without_flag(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("measure() ran without SCE_NTT_AUTOTUNE=1")

    monkeypatch.setattr(autotune, "measure", boom)
    assert autotune.resolve_tile("ntt_banks", 2, 256, 100) == \
        autotune.DEFAULT_TILE


def test_no_measurement_inside_trace(monkeypatch):
    """Even with the flag on, a resolve inside a jit trace must take the
    deterministic path — timing a trace would poison the cache AND mint
    a new jit signature per candidate."""
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "1")

    def boom(*a, **kw):
        raise AssertionError("measure() ran inside a jit trace")

    monkeypatch.setattr(autotune, "measure", boom)
    import jax.numpy as jnp

    @jax.jit
    def prog(x):
        t = autotune.resolve_tile("ntt_banks", 2, 256, 100)
        return x * t

    out = prog(jnp.ones((2,), jnp.uint32))
    assert int(out[0]) == autotune.DEFAULT_TILE


def test_measure_gated_flag_runs_fake_runner(monkeypatch):
    """With the flag on and outside a trace, resolve measures once and
    caches the argmin; the second resolve is a pure cache hit."""
    calls = []

    def fake_runner(k, n, b):
        def run(tile):
            calls.append(tile)
            return np.zeros((1,), np.uint32)
        return run

    times = {8: 9.0, 16: 1.0, 32: 5.0}

    def fake_measure_time(run, tile):
        run(tile)
        return times[tile]

    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "1")
    monkeypatch.setitem(autotune._RUNNERS, "fake_fam", fake_runner)
    # patch the timer indirection: drive measure() through a shim that
    # reuses its candidate/caching logic but deterministic "times"
    real_measure = autotune.measure

    def shim(family, k, n, b, *, reps=3, dtype="uint32"):
        key = (jax.default_backend(), family, int(k), int(n), int(b),
               "uint32")
        run = autotune._RUNNERS[family](k, n, b)
        cands = sorted({autotune.clamp(t, b) for t in
                        autotune.CANDIDATE_TILES})
        best = min(cands, key=lambda t: fake_measure_time(run, t))
        autotune._MEM[key] = best
        return best

    monkeypatch.setattr(autotune, "measure", shim)
    got = autotune.resolve_tile("fake_fam", 1, 128, 64)
    assert got == 16 and calls == [8, 16, 32]
    calls.clear()
    monkeypatch.setattr(autotune, "measure", real_measure)
    assert autotune.resolve_tile("fake_fam", 1, 128, 64) == 16
    assert calls == []      # cache hit, no re-measure


def test_real_measure_smoke(monkeypatch):
    """The real timer path end to end on a tiny workload: returns a
    candidate, caches it, and ensure() reuses the entry."""
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "1")
    got = autotune.measure("ntt", 1, 64, 2, reps=1)
    assert got in (1, 2)
    assert autotune.resolve_tile("ntt", 1, 64, 2) == got
    assert autotune.ensure("ntt", 1, 64, 2) == got


def test_disk_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "tiles.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    key = (jax.default_backend(), "ntt_banks", 3, 1024, 16, "uint32")
    autotune._MEM[key] = 16
    autotune._save_disk()
    data = json.loads(path.read_text())
    assert data["entries"]["|".join(str(p) for p in key)] == 16
    # a fresh process (simulated by clear + reload) sees the entry
    autotune.clear()
    autotune._DISK_LOADED = False
    assert autotune.resolve_tile("ntt_banks", 3, 1024, 16) == 16


def test_disk_cache_corrupt_is_ignored(tmp_path, monkeypatch):
    path = tmp_path / "tiles.json"
    path.write_text("{ not json")
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune._DISK_LOADED = False
    assert autotune.resolve_tile("ntt", 1, 256, 100) == autotune.DEFAULT_TILE


def test_dump_and_table(tmp_path):
    key = (jax.default_backend(), "dyadic_mul", 1, 512, 8, "uint32")
    autotune._MEM[key] = 2
    t = autotune.table()
    assert t["backend"] == jax.default_backend()
    out = tmp_path / "snap.json"
    autotune.dump(str(out))
    assert json.loads(out.read_text())["entries"][
        "|".join(str(p) for p in key)] == 2


def test_shard_batch_rule():
    assert autotune.shard_batch(32, 1) == 32
    assert autotune.shard_batch(32, 4) == 8
    assert autotune.shard_batch(33, 4) == 9      # ceil, not floor
    assert autotune.shard_batch(2, 4) == 1       # more shards than rows
    assert autotune.shard_batch(32) == 32        # default: unsharded
    assert autotune.shard_batch(0, 4) == 0       # empty batch unchanged


def test_resolve_uses_per_shard_cache_entry(monkeypatch):
    """The PR 8 regression: a 4-device mesh over b=32 dispatches b=8 per
    shard, so resolution must hit the b=8 cache entry — keying on the
    global batch would tune for a grid no device ever runs."""
    k, n = 3, 1024
    be = jax.default_backend()
    monkeypatch.setitem(autotune._MEM,
                        (be, "serve_batch", k, n, 8, "uint32"), 16)
    monkeypatch.setitem(autotune._MEM,
                        (be, "serve_batch", k, n, 32, "uint32"), 24)
    # unsharded resolve sees the global-batch entry...
    assert autotune.resolve_tile("serve_batch", k, n, 32) == 24
    # ...the 4-shard resolve sees the per-shard one (clamped to b=8)
    assert autotune.resolve_tile("serve_batch", k, n, 32, shards=4) == 8
    # ensure() follows the same funnel
    assert autotune.ensure("serve_batch", k, n, 32, shards=4) == 8
    # per-shard clamp: 4 shards over b=4 -> one row each, tile 1
    assert autotune.resolve_tile("serve_batch", k, n, 4, shards=4) == 1
    # explicit tile still outranks, clamped to the per-shard batch
    assert autotune.resolve_tile("serve_batch", k, n, 32, tile=32,
                                 shards=4) == 8


def test_serve_engine_resolves_per_shard_tile(monkeypatch):
    """End to end through the serve engine with a FAKE 4-device mesh:
    the engine must resolve its batch tile against the per-shard batch
    (hitting a seeded b=8 entry) and size groups to tile * devices."""
    from repro.fhe import serve
    from repro.fhe.ckks import CkksContext

    ctx = CkksContext(n=64, levels=2, seed=3)
    plan = ctx.plan()
    k = len(plan.ctx.qs)
    be = jax.default_backend()
    monkeypatch.setitem(autotune._MEM,
                        (be, "serve_batch", k, plan.n, 8, "uint32"), 8)
    monkeypatch.setitem(autotune._MEM,
                        (be, "serve_batch", k, plan.n, 32, "uint32"), 16)
    monkeypatch.setattr(type(plan), "mesh_devices",
                        property(lambda self: 4))
    eng = serve.CkksServeEngine(plan)
    assert eng.devices == 4
    assert eng.batch_tile == 8          # the b=8 per-shard entry, not b=32
    assert eng.group_tile == 32         # tile x devices
    assert eng.max_batch == 128         # 4 x group_tile default


def test_dtype_keys_do_not_collide(monkeypatch):
    """The scheme-collision regression: a u16 small-ring family and the
    u32 CKKS family with identical (family, k, n, b) resolve through
    DIFFERENT cache entries."""
    be = jax.default_backend()
    monkeypatch.setitem(autotune._MEM,
                        (be, "ntt_banks", 1, 256, 64, "uint32"), 32)
    monkeypatch.setitem(autotune._MEM,
                        (be, "ntt_banks", 1, 256, 64, "uint16"), 16)
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64) == 32
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64,
                                 dtype="uint16") == 16
    # a u16 entry alone must NOT satisfy a u32 lookup (or vice versa)
    autotune.clear()
    monkeypatch.setitem(autotune._MEM,
                        (be, "ntt_banks", 1, 256, 64, "uint16"), 16)
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64) == \
        autotune.DEFAULT_TILE


def test_disk_cache_roundtrips_dtype(tmp_path, monkeypatch):
    """u16 and u32 entries survive a save/load cycle as distinct keys."""
    path = tmp_path / "tiles.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    be = jax.default_backend()
    autotune._MEM[(be, "ntt_banks", 1, 256, 64, "uint32")] = 16
    autotune._MEM[(be, "ntt_banks", 1, 256, 64, "uint16")] = 32
    autotune._save_disk()
    entries = json.loads(path.read_text())["entries"]
    assert entries[f"{be}|ntt_banks|1|256|64|uint32"] == 16
    assert entries[f"{be}|ntt_banks|1|256|64|uint16"] == 32
    autotune.clear()
    autotune._DISK_LOADED = False
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64) == 16
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64,
                                 dtype="uint16") == 32


def test_disk_cache_old_format_ignored_with_warning(tmp_path, monkeypatch):
    """Pre-dtype (5-part) persisted entries are skipped with a warning —
    never misread as some dtype's tile."""
    path = tmp_path / "tiles.json"
    be = jax.default_backend()
    path.write_text(json.dumps({"entries": {
        f"{be}|ntt_banks|1|256|64": 32,              # old 5-part key
        f"{be}|ntt_banks|1|256|64|uint16": 16,       # current format
    }}))
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune.clear()
    autotune._DISK_LOADED = False
    with pytest.warns(UserWarning, match="old-format"):
        autotune._load_disk()
    # the stale entry resolved nothing; the 6-part one loaded fine
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64) == \
        autotune.DEFAULT_TILE
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64,
                                 dtype="uint16") == 16


def test_measure_non_u32_caches_default_without_timing(monkeypatch):
    """A u16 family never times the u32 runners — it caches the clamped
    static default under its own key instead."""
    def boom(*a, **kw):
        raise AssertionError("u32 runner invoked for a uint16 measure")

    monkeypatch.setitem(autotune._RUNNERS, "ntt_banks", boom)
    got = autotune.measure("ntt_banks", 1, 256, 64, dtype="uint16")
    assert got == autotune.DEFAULT_TILE
    assert autotune.resolve_tile("ntt_banks", 1, 256, 64,
                                 dtype="uint16") == got


def test_ops_honors_env_pin(monkeypatch):
    """End to end: the pin reaches the kernel dispatch (captured via the
    kernel wrapper) and is still clamped to the batch."""
    from repro.kernels import ntt_kernel
    p = make_ntt_params(256)
    seen = {}

    def fake_fwd(x2, *args, tile, **kw):
        seen["tile"] = tile
        import jax.numpy as jnp
        return jnp.zeros_like(x2)

    monkeypatch.setattr(ntt_kernel, "ntt_fwd_pallas", fake_fwd)
    monkeypatch.setenv(autotune.ENV_PIN, "16")
    rng = np.random.default_rng(7)
    x = rng.integers(0, p.q, size=(32, 256), dtype=np.uint32)
    ops.ntt(x, p, use_pallas=True)
    assert seen["tile"] == 16
    ops.ntt(x[:5], p, use_pallas=True)          # pin clamped to the batch
    assert seen["tile"] == 5
