"""TuningCache persistence: JSON round-trips, corrupt/partial cache files
falling back to seeded defaults (never raising), and key-collision behavior
across the ``mode`` (interpret vs hw) and format/scheme axes."""

import json
import warnings

import jax.numpy as jnp
import pytest

from repro.kernels import ops as kops
from repro.kernels.ops import TuneEntry, TuningCache


@pytest.fixture
def fresh_cache():
    cache = kops.tuning_cache()
    prev_enabled, prev_entries, prev_sweeps = (
        cache.enabled, dict(cache.entries), cache.sweeps,
    )
    cache.clear()
    yield cache
    cache.enabled = prev_enabled
    cache.entries = prev_entries
    cache.sweeps = prev_sweeps


# --------------------------------------------------------------------------- #
# round-trip                                                                   #
# --------------------------------------------------------------------------- #


def test_roundtrip_preserves_blocks_ms_and_marks_loaded(tmp_path):
    c = TuningCache(enabled=False)
    k1 = TuningCache.key("matmul", 64, 128, 256, jnp.float32, "dense", False)
    k2 = TuningCache.key("qmatmul", 64, 128, 256, jnp.int8, "dense+w8a8", False)
    c.entries[k1] = TuneEntry((256, 128, 128), "swept", 0.42)
    c.entries[k2] = TuneEntry((128, 128, 512), "swept", 0.17)
    p = str(tmp_path / "tune.json")
    c.save(p)
    c2 = TuningCache(enabled=False).load(p)
    assert c2.entries[k1].blocks == (256, 128, 128)
    assert c2.entries[k1].ms == pytest.approx(0.42)
    assert c2.entries[k2].blocks == (128, 128, 512)
    assert all(e.source == "loaded" for e in c2.entries.values())


def test_roundtrip_drops_default_placeholders(tmp_path):
    """Seeded defaults were never measured: persisting them would block
    future sweeps of those shapes in other processes."""
    c = TuningCache(enabled=False)
    c.resolve("matmul", 8, 8, 8, jnp.float32, "dense", True)  # records a default
    c.entries[TuningCache.key("matmul", 16, 16, 16, jnp.float32, "dense", True)] = (
        TuneEntry((64, 128, 128), "swept", 1.0)
    )
    p = str(tmp_path / "tune.json")
    c.save(p)
    entries = json.loads(open(p).read())["entries"]
    assert len(entries) == 1
    assert next(iter(entries.values()))["source"] == "swept"


def test_save_without_path_raises():
    c = TuningCache(enabled=False, path=None)
    with pytest.raises(ValueError, match="no cache path"):
        c.save()


# --------------------------------------------------------------------------- #
# corrupt / partial cache files fall back to seeded defaults                   #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "payload",
    [
        "{ not json at all",                                  # syntactically broken
        json.dumps({"version": 1}),                           # missing entries
        json.dumps({"version": 1, "entries": {"k": {}}}),     # entry missing blocks
        json.dumps({"version": 1, "entries": {"k": None}}),   # entry wrong type
    ],
)
def test_corrupt_cache_file_warns_and_uses_defaults(tmp_path, payload):
    p = tmp_path / "tune.json"
    p.write_text(payload)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c = TuningCache(enabled=False, path=str(p))
    assert any("ignoring unreadable tuning cache" in str(x.message) for x in w)
    # the cache still works: unknown keys resolve to the seeded defaults
    assert c.resolve("matmul", 8, 8, 8, jnp.float32, "dense", True) == (128, 128, 128, 1)
    assert c.resolve("qmatmul", 8, 8, 8, jnp.int8, "dense+w8a8", True) == (128, 128, 128, 1)


def test_missing_cache_file_is_silently_fresh(tmp_path):
    c = TuningCache(enabled=False, path=str(tmp_path / "nope.json"))
    assert c.entries == {}


# --------------------------------------------------------------------------- #
# key collisions                                                               #
# --------------------------------------------------------------------------- #


def test_interpret_and_hw_modes_never_share_a_winner(fresh_cache):
    """Interpret-mode sweeps time Python, not silicon: an interpret winner
    must never shadow (or be returned for) a real-hardware lookup."""
    shape = ("matmul", 64, 128, 256, jnp.float32, "dense")
    k_int = TuningCache.key(*shape, True)
    k_hw = TuningCache.key(*shape, False)
    assert k_int != k_hw
    fresh_cache.entries[k_int] = TuneEntry((64, 128, 128), "swept", 9.9)
    assert fresh_cache.lookup(*shape, False) is None
    # hw resolve falls back to the seeded default, not the interpret winner
    assert fresh_cache.resolve(*shape, False) == TuningCache.DEFAULTS["matmul"]
    # and the interpret entry is untouched
    assert fresh_cache.entries[k_int].blocks == (64, 128, 128)


def test_format_and_scheme_axes_key_separately():
    keys = {
        TuningCache.key("matmul", 8, 8, 8, jnp.float32, "dense", True),
        TuningCache.key("matmul", 8, 8, 8, jnp.float32, "dense+e2s1", True),
        TuningCache.key("matmul", 8, 8, 8, jnp.float32, "colcompact", True),
        TuningCache.key("qmatmul", 8, 8, 8, jnp.float32, "dense+w8", True),
        TuningCache.key("qmatmul", 8, 8, 8, jnp.int8, "dense+w8a8", True),
        TuningCache.key("bsr_matmul", 8, 8, 8, jnp.float32, "pbcsr", True),
        TuningCache.key("bsr_matmul", 8, 8, 8, jnp.float32, "pbcsr+e1s1", True),
    }
    assert len(keys) == 7  # no two collapse


def test_loaded_entries_survive_resolve_and_block_sweeps(fresh_cache):
    """A loaded winner is authoritative: resolve returns it without
    sweeping even when tuning is enabled."""
    shape = ("matmul", 64, 128, 256, jnp.float32, "dense")
    key = TuningCache.key(*shape, True)
    fresh_cache.entries[key] = TuneEntry((256, 128, 128), "loaded", 0.5)
    fresh_cache.enabled = True
    called = []

    def runner(*blocks):
        called.append(blocks)

    assert fresh_cache.resolve(*shape, True, runner=runner) == (256, 128, 128)
    assert not called and fresh_cache.sweeps == 0


# --------------------------------------------------------------------------- #
# PR 6: pipeline-depth / block_c key-family extension                          #
# --------------------------------------------------------------------------- #


def test_matmul_defaults_carry_pipeline_depth_and_conv_block_c():
    """The matmul/qmatmul block tuple grew a 4th pipeline-depth field and
    conv2d a 3rd block_c field; defaults pin the legacy behavior (depth 1 =
    compiler-scheduled grid-K, block_c 0 = resident full-K)."""
    assert TuningCache.DEFAULTS["matmul"] == (128, 128, 128, 1)
    assert TuningCache.DEFAULTS["qmatmul"] == (128, 128, 128, 1)
    assert TuningCache.DEFAULTS["conv2d"] == (8, 128, 0)
    # candidate grids include pipelined / tiled-K entries
    assert any(c[3] >= 2 for c in TuningCache.CANDIDATES["matmul"])
    assert any(c[3] >= 2 for c in TuningCache.CANDIDATES["qmatmul"])
    assert any(c[2] > 0 for c in TuningCache.CANDIDATES["conv2d"])


def test_legacy_block_tuples_normalize_without_colliding():
    """Entries cached before the field extension (3-tuple matmul, 2-tuple
    conv) still resolve: the normalizers extend them with the legacy-pinned
    values instead of keying them separately."""
    assert kops._blocks4((256, 128, 128)) == (256, 128, 128, 1)
    assert kops._blocks4((128, 128, 128, 2)) == (128, 128, 128, 2)
    assert kops._conv_blocks3((8, 128)) == (8, 128, 0)
    assert kops._conv_blocks3((8, 128, 64)) == (8, 128, 64)


def test_extended_block_tuples_json_round_trip(tmp_path):
    """4-field matmul winners and 3-field conv winners survive save/load
    bit-exactly (depth/block_c are part of the value, not the key, so no
    old-format key can collide with them)."""
    c = TuningCache(enabled=False)
    km = TuningCache.key("matmul", 64, 128, 512, jnp.float32, "dense", False)
    kc = TuningCache.key_nd(
        "conv2d", (1, 256, 16, 16, 64, 3, 3, 1), jnp.float32, "dense+f32", False
    )
    c.entries[km] = TuneEntry((128, 128, 256, 2), "swept", 0.3)
    c.entries[kc] = TuneEntry((8, 128, 64), "swept", 0.7)
    p = str(tmp_path / "tune.json")
    c.save(p)
    c2 = TuningCache(enabled=False).load(p)
    assert c2.entries[km].blocks == (128, 128, 256, 2)
    assert c2.entries[kc].blocks == (8, 128, 64)
    assert all(e.source == "loaded" for e in c2.entries.values())


def test_loaded_pipelined_winner_blocks_sweeps(fresh_cache):
    """A loaded depth-2 winner is authoritative exactly like a legacy one:
    resolve returns it verbatim, no sweep, and the stats ledger records a
    hit rather than a miss."""
    shape = ("matmul", 64, 128, 512, jnp.float32, "dense")
    key = TuningCache.key(*shape, True)
    fresh_cache.entries[key] = TuneEntry((128, 128, 256, 2), "loaded", 0.4)
    fresh_cache.enabled = True
    called = []
    got = fresh_cache.resolve(*shape, True, runner=lambda *b: called.append(b))
    assert got == (128, 128, 256, 2)
    assert not called and fresh_cache.sweeps == 0
    assert fresh_cache.stats["matmul"] == {"hits": 1, "misses": 0, "sweeps": 0}


def test_ops_filter_restricts_sweeps_but_not_lookups(fresh_cache):
    """The tune CLI's --ops filter: excluded families never sweep (they
    resolve to defaults) while included families sweep normally; cached
    winners still serve everyone."""
    fresh_cache.enabled = True
    fresh_cache.ops_filter = frozenset({"conv2d"})
    swept = []

    def runner(*blocks):
        swept.append(blocks)
        return jnp.zeros(())

    shape = ("matmul", 64, 128, 128, jnp.float32, "dense")
    got = fresh_cache.resolve(*shape, True, runner=runner)
    assert got == TuningCache.DEFAULTS["matmul"] and not swept
    assert fresh_cache.stats["matmul"]["sweeps"] == 0
    conv_shape = (1, 8, 8, 8, 4, 3, 3, 1)
    fresh_cache.resolve_nd(
        "conv2d", conv_shape, jnp.float32, "dense+f32", True, runner=runner
    )
    assert swept  # the included family swept its candidate grid
    assert fresh_cache.stats["conv2d"]["sweeps"] == 1
    # a cached winner is returned regardless of the filter
    key = TuningCache.key(*shape, True)
    fresh_cache.entries[key] = TuneEntry((64, 128, 128, 1), "swept", 0.2)
    assert fresh_cache.resolve(*shape, True) == (64, 128, 128, 1)


def test_sweep_counts_rejected_candidates(fresh_cache):
    """A candidate that raises (a tile the compiler refuses) is counted per
    op and exception type, never mistaken for a slow one."""
    fresh_cache.enabled = True
    first = TuningCache.CANDIDATES["matmul"][0]

    def runner(*blocks):
        if blocks == first:
            raise ValueError("refused")
        return jnp.zeros(())

    got = fresh_cache.resolve("matmul", 64, 128, 128, jnp.float32, "dense", True,
                              runner=runner)
    assert got != first
    assert kops.tune_rejected_counts() == {"matmul/ValueError": 1}


def test_sweep_raises_when_every_candidate_fails(fresh_cache):
    fresh_cache.enabled = True

    def runner(*blocks):
        raise RuntimeError("refused")

    with pytest.raises(RuntimeError, match="every matmul tuning candidate failed"):
        fresh_cache.resolve("matmul", 64, 128, 128, jnp.float32, "dense", True,
                            runner=runner)
    n = len(TuningCache.CANDIDATES["matmul"])
    assert kops.tune_rejected_counts() == {"matmul/RuntimeError": n}
    assert not fresh_cache.entries  # no silent default was seeded


def test_stats_report_csv_counts_per_family(fresh_cache):
    fresh_cache.resolve("matmul", 8, 8, 8, jnp.float32, "dense", True)   # miss
    fresh_cache.resolve("matmul", 8, 8, 8, jnp.float32, "dense", True)   # hit
    fresh_cache.resolve("qmatmul", 8, 8, 8, jnp.int8, "dense+w8a8", True)
    report = fresh_cache.stats_report()
    lines = report.splitlines()
    assert lines[0] == "family,hits,misses,sweeps"
    assert "matmul,1,1,0" in lines
    assert "qmatmul,0,1,0" in lines
    # clear() wipes the ledger with the entries
    fresh_cache.clear()
    assert fresh_cache.stats_report() == "family,hits,misses,sweeps"


# --------------------------------------------------------------------------- #
# crash-safe (atomic) save                                                     #
# --------------------------------------------------------------------------- #


def _cache_with_entry(key_dims=(64, 128, 256), blocks=(256, 128, 128)):
    c = TuningCache(enabled=False)
    k = TuningCache.key("matmul", *key_dims, jnp.float32, "dense", False)
    c.entries[k] = TuneEntry(blocks, "swept", 0.5)
    return c, k


def test_interrupted_save_leaves_previous_file_intact(tmp_path, monkeypatch):
    """A save that dies mid-write (simulated dump failure) must leave the
    previously saved JSON byte-identical and valid -- the write lands in a
    temp file that never replaces the destination."""
    c, k = _cache_with_entry()
    p = str(tmp_path / "tune.json")
    c.save(p)
    before = open(p).read()
    json.loads(before)  # valid baseline

    def boom(obj, f, **kw):
        f.write('{"version": 1, "entr')  # truncated garbage, then die
        raise RuntimeError("disk full")

    monkeypatch.setattr(json, "dump", boom)
    with pytest.raises(RuntimeError, match="disk full"):
        c.save(p)
    assert open(p).read() == before  # destination untouched
    assert json.loads(open(p).read())["entries"]  # still parseable
    leftovers = [f for f in tmp_path.iterdir() if f.name != "tune.json"]
    assert leftovers == []  # temp file cleaned up on failure


def test_concurrent_saves_never_expose_truncated_json(tmp_path):
    """Hammer save() from two threads while a reader loads in a loop: the
    atomic rename means every observed file state parses as complete JSON
    (the pre-fix plain open(path, 'w') interleaves and truncates)."""
    import threading

    c1, _ = _cache_with_entry((64, 128, 256), (256, 128, 128))
    c2, _ = _cache_with_entry((32, 64, 512), (128, 128, 512))
    # make the payloads different sizes so torn writes would be visible
    for i in range(50):
        k = TuningCache.key("conv2d", 8 + i, 8, 8, jnp.float32, "dense", True)
        c2.entries[k] = TuneEntry((1, 8, 64, 64, 1), "swept", float(i))
    p = str(tmp_path / "tune.json")
    c1.save(p)
    stop = threading.Event()
    errors = []

    def writer(c):
        while not stop.is_set():
            try:
                c.save(p)
            except Exception as e:  # pragma: no cover - fails the test below
                errors.append(e)
                return

    threads = [threading.Thread(target=writer, args=(c,)) for c in (c1, c2)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            payload = json.loads(open(p).read())  # must never raise
            assert payload["version"] == 1
            assert len(payload["entries"]) in (1, 51)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert errors == []


def test_save_still_returns_path_and_roundtrips(tmp_path):
    """The atomic rewrite keeps the external contract: returns the path,
    and an immediate load sees exactly what was saved."""
    c, k = _cache_with_entry()
    p = str(tmp_path / "sub")
    import os

    os.makedirs(p)
    target = os.path.join(p, "tune.json")
    assert c.save(target) == target
    c2 = TuningCache(enabled=False).load(target)
    assert c2.entries[k].blocks == (256, 128, 128)
