"""PersistentSynthesisCache hardening (ISSUE 4 satellite): npz round-trip
across processes, corrupted/truncated file handling (raise or rebuild —
never garbage), and eviction-stat accounting under the row limit."""

import subprocess
import sys

import numpy as np
import pytest

from repro.core.accelerator import design_space_soa
from repro.core.confighash import config_digests
from repro.core.synthesis import (REPORT_COLUMNS, PersistentSynthesisCache,
                                  synthesize_soa)


def _small_soa(n: int | None = None):
    soa = next(design_space_soa())              # one SoA for the full grid
    if n is not None:
        soa = {k: v[:n] for k, v in soa.items()}
    return soa


# ---------------------------------------------------------------------------
# round-trip
# ---------------------------------------------------------------------------

def test_save_load_round_trip_same_process(tmp_path):
    path = tmp_path / "synth.npz"
    cache = PersistentSynthesisCache(path)
    soa = _small_soa(64)
    cols = cache.synthesize(soa)
    assert cache.misses == 64 and cache.hits == 0
    assert cache.save() == 64

    warm = PersistentSynthesisCache(path)
    assert len(warm) == 64
    mask, cols2 = warm.lookup(config_digests(soa))
    assert mask.all()
    for c in REPORT_COLUMNS:
        assert np.array_equal(cols2[c], cols[c]), c


def test_round_trip_across_processes(tmp_path):
    """A cache written by another interpreter hydrates bit-identically —
    the npz format carries no in-process state."""
    import os
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    path = tmp_path / "synth.npz"
    writer = (
        "import sys; sys.path.insert(0, {src!r})\n"
        "from repro.core.accelerator import design_space_soa\n"
        "from repro.core.synthesis import PersistentSynthesisCache\n"
        "soa = {{k: v[:48] for k, v in next(design_space_soa()).items()}}\n"
        "c = PersistentSynthesisCache({path!r})\n"
        "c.synthesize(soa)\n"
        "print(c.save())\n"
    ).format(src=str(root / "src"), path=str(path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", writer], cwd=str(root),
                         capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().endswith("48")

    soa = _small_soa(48)
    cache = PersistentSynthesisCache(path)
    assert len(cache) == 48
    mask, cols = cache.lookup(config_digests(soa))
    assert mask.all() and cache.hits == 48 and cache.misses == 0
    fresh = synthesize_soa(soa)
    for c in REPORT_COLUMNS:
        assert np.array_equal(cols[c], fresh[c]), c


# ---------------------------------------------------------------------------
# corrupted / truncated / structurally wrong files
# ---------------------------------------------------------------------------

def _saved_cache(tmp_path, n=32):
    path = tmp_path / "synth.npz"
    cache = PersistentSynthesisCache(path)
    cache.synthesize(_small_soa(n))
    cache.save()
    return path


def test_truncated_file_rebuilds_in_constructor(tmp_path):
    path = _saved_cache(tmp_path)
    raw = path.read_bytes()
    path.write_bytes(raw[: len(raw) // 2])
    with pytest.warns(RuntimeWarning, match="unreadable"):
        cache = PersistentSynthesisCache(path)
    assert len(cache) == 0                      # rebuilt, not garbage
    # and it still works: synthesize misses, then saves over the bad file
    cols = cache.synthesize(_small_soa(8))
    assert np.isfinite(cols["area_mm2"]).all()
    cache.save()
    assert len(PersistentSynthesisCache(path)) == 8


def test_garbage_bytes_rebuild_and_explicit_load_raises(tmp_path):
    path = tmp_path / "synth.npz"
    path.write_bytes(b"this is not an npz file at all")
    with pytest.warns(RuntimeWarning, match="unreadable"):
        cache = PersistentSynthesisCache(path)
    assert len(cache) == 0
    with pytest.raises(Exception):
        cache.load(path)                        # explicit load surfaces it


def test_missing_columns_raise_not_merge(tmp_path):
    path = tmp_path / "synth.npz"
    np.savez(path, keys=np.zeros((4, 2), dtype=np.uint64))
    fresh = PersistentSynthesisCache()
    with pytest.raises(ValueError, match="missing array"):
        fresh.load(path)
    assert len(fresh) == 0


def test_wrong_key_shape_and_nonfinite_values_raise(tmp_path):
    path = tmp_path / "synth.npz"
    cols = {c: np.ones(4) for c in REPORT_COLUMNS}
    np.savez(path, keys=np.zeros((4, 3), dtype=np.uint64), **cols)
    with pytest.raises(ValueError, match="keys shape"):
        PersistentSynthesisCache().load(path)

    bad = dict(cols, area_mm2=np.array([1.0, np.nan, 1.0, 1.0]))
    np.savez(path, keys=np.zeros((4, 2), dtype=np.uint64), **bad)
    with pytest.raises(ValueError, match="non-finite"):
        PersistentSynthesisCache().load(path)

    ragged = dict(cols, power_mw=np.ones(3))
    np.savez(path, keys=np.zeros((4, 2), dtype=np.uint64), **ragged)
    with pytest.raises(ValueError):
        PersistentSynthesisCache().load(path)


# ---------------------------------------------------------------------------
# eviction accounting under the row limit
# ---------------------------------------------------------------------------

def test_eviction_stats_under_row_limit():
    cache = PersistentSynthesisCache(max_rows=40)
    soa = _small_soa(100)
    cache.synthesize(soa)
    # every insert overflow compacts down to max_rows // 2 newest rows
    assert len(cache) <= 40
    assert cache.evictions == 100 - len(cache)
    assert cache.misses == 100 and cache.hits == 0

    # the newest rows survive: re-synthesizing the tail hits, the head
    # misses and re-enters
    tail = {k: v[-len(cache):] for k, v in soa.items()}
    cache.synthesize(tail)
    assert cache.hits == len(tail["pe_rows"])

    head = {k: v[:20] for k, v in soa.items()}
    before = cache.evictions
    cache.synthesize(head)
    assert cache.misses == 120
    assert cache.evictions >= before            # may or may not compact

    # eviction never loses *correctness*: evicted rows re-synthesize to
    # the same values (pure function of the digest)
    fresh = synthesize_soa(head)
    _, cols = cache.lookup(config_digests(head))
    for c in REPORT_COLUMNS:
        assert np.array_equal(cols[c], fresh[c]), c


def test_clear_keeps_cap_and_path(tmp_path):
    path = tmp_path / "synth.npz"
    cache = PersistentSynthesisCache(path, max_rows=16)
    cache.synthesize(_small_soa(8))
    cache.clear()
    assert len(cache) == 0 and cache.hits == 0 and cache.misses == 0
    assert cache.max_rows == 16 and cache.path == path


# ---------------------------------------------------------------------------
# atomic persistence + state export/import (ISSUE 7 satellites)
# ---------------------------------------------------------------------------

def test_save_is_atomic_under_write_failure(tmp_path, monkeypatch):
    """A crash mid-save must leave the previous on-disk cache intact and
    no temp litter — save() writes a sibling temp file and renames."""
    path = tmp_path / "synth.npz"
    cache = PersistentSynthesisCache(path)
    soa = _small_soa(32)
    cache.synthesize(soa)
    assert cache.save() == 32

    cache.synthesize(_small_soa(64))            # 32 new rows pending

    def boom(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(np, "savez_compressed", boom)
    with pytest.raises(OSError):
        cache.save()
    monkeypatch.undo()

    leftovers = [p for p in tmp_path.iterdir() if p.name != "synth.npz"]
    assert leftovers == []                      # temp file cleaned up
    survivor = PersistentSynthesisCache(path)   # old file still valid
    assert len(survivor) == 32
    mask, cols = survivor.lookup(config_digests(soa))
    assert mask.all()


def test_export_import_state_roundtrip(tmp_path):
    src = PersistentSynthesisCache(tmp_path / "a.npz")
    soa = _small_soa(48)
    src.synthesize(soa)
    src.synthesize(soa)                         # 48 hits
    state = src.export_state()

    dst = PersistentSynthesisCache(tmp_path / "b.npz")
    dst.synthesize(_small_soa(8))               # overwritten by import
    dst.import_state(state)
    assert len(dst) == len(src) == 48
    assert (dst.hits, dst.misses, dst.evictions) == (48, 48, 0)
    mask, cols = dst.lookup(config_digests(soa))
    assert mask.all()
    fresh = synthesize_soa(soa)
    for c in REPORT_COLUMNS:
        assert np.array_equal(cols[c], fresh[c]), c

    # the exported dict is a snapshot: mutating the source afterwards
    # must not retroactively change an already-captured state
    src.synthesize(_small_soa(64))
    assert len(state["keys"]) == 48


def test_import_state_validates_shapes(tmp_path):
    cache = PersistentSynthesisCache(tmp_path / "c.npz")
    state = {"keys": np.zeros((4, 2), dtype=np.uint64),
             "vals": np.zeros((3, len(REPORT_COLUMNS))),
             "hits": 0, "misses": 0, "evictions": 0}
    with pytest.raises(ValueError):
        cache.import_state(state)


# ---------------------------------------------------------------------------
# the array index against a plain dict model of the cache's semantics
# ---------------------------------------------------------------------------

def _lanes(u64):
    """The 4-lane digest whose ``digests_to_u64`` is ``u64``."""
    u64 = np.asarray(u64, dtype=np.uint64).reshape(-1, 2)
    lo, hi = np.uint64(0xFFFFFFFF), np.uint64(32)
    return (u64[:, 0] & lo, u64[:, 0] >> hi, u64[:, 1] & lo, u64[:, 1] >> hi)


def _cols(vals):
    return {c: vals[:, j] for j, c in enumerate(REPORT_COLUMNS)}


class _DictCache:
    """The cache's semantics written plainly: rows in lists, an index
    from key to its newest row, compaction that keeps the newest half and
    re-indexes what it keeps."""

    def __init__(self, max_rows):
        self.max_rows = max_rows
        self.keys, self.vals, self.index = [], [], {}
        self.hits = self.misses = self.evictions = 0

    def _compact(self):
        if self.max_rows is None or len(self.keys) <= self.max_rows:
            return
        drop = len(self.keys) - self.max_rows // 2
        self.keys, self.vals = self.keys[drop:], self.vals[drop:]
        self.evictions += drop
        self.index = {k: i for i, k in enumerate(self.keys)}

    def lookup(self, u64):
        rows = [self.index.get(k, -1) for k in map(tuple, u64.tolist())]
        vals = np.zeros((len(rows), len(REPORT_COLUMNS)))
        for i, r in enumerate(rows):
            if r >= 0:
                vals[i] = self.vals[r]
        mask = np.array(rows, dtype=np.intp) >= 0
        self.hits += int(mask.sum())
        self.misses += int((~mask).sum())
        return mask, vals

    def insert(self, u64, vals):
        before = len(self.index)
        for k, v in zip(map(tuple, u64.tolist()), vals):
            self.index[k] = len(self.keys)
            self.keys.append(k)
            self.vals.append(v)
        self._compact()
        return len(self.index) - before

    def load(self, u64, vals):
        before = len(self.keys)
        for k, v in zip(map(tuple, u64.tolist()), vals):
            if k not in self.index:
                self.index[k] = len(self.keys)
                self.keys.append(k)
                self.vals.append(v)
        self._compact()
        return len(self.keys) - before

    def import_state(self, u64, vals, hits, misses, evictions):
        self.keys = list(map(tuple, u64.tolist()))
        self.vals = list(vals)
        self.index = {k: i for i, k in enumerate(self.keys)}
        self.hits, self.misses, self.evictions = hits, misses, evictions
        self._compact()


def _key_pool(rng, n):
    """``n`` distinct keys with collisions planted: pairs sharing the
    first word, and runs sharing the low 32 bits (one home slot)."""
    pool = rng.integers(0, 2**63, size=(n, 2), dtype=np.uint64)
    pool[1::7, 0] = pool[0::7, 0][:len(pool[1::7])]          # first word
    low = np.uint64(0xFFFFFFFF)
    pool[2::5, 0] = (pool[2::5, 0] & ~low) | (pool[3, 0] & low)   # slot
    return np.unique(pool, axis=0)


def _assert_same(cache, model):
    assert (cache.hits, cache.misses, cache.evictions, len(cache)) == \
        (model.hits, model.misses, model.evictions, len(model.keys))
    state = cache.export_state()
    want_keys = np.array(model.keys, dtype=np.uint64).reshape(-1, 2)
    want_vals = np.array(model.vals).reshape(-1, len(REPORT_COLUMNS))
    assert np.array_equal(state["keys"], want_keys)
    assert np.array_equal(state["vals"], want_vals)


@pytest.mark.parametrize("max_rows", [None, 16, 61])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_index_matches_dict_model(tmp_path, seed, max_rows):
    """Random lookup / insert / load / import_state sequences, with keys
    repeated within and across batches and colliding in a word or a
    slot: masks, values, counters, return values and the exported rows
    equal the dict model's after every step.  Values differ per row, so
    pointing at any row but the newest shows."""
    rng = np.random.default_rng(seed)
    pool = _key_pool(rng, 150)
    cache, model = PersistentSynthesisCache(max_rows=max_rows), \
        _DictCache(max_rows)
    path = tmp_path / "synth.npz"
    rebuilds = 0
    for step in range(400):
        u64 = pool[rng.integers(0, len(pool), size=rng.integers(0, 48))]
        vals = rng.random((len(u64), len(REPORT_COLUMNS)))
        op = rng.random()
        floor = cache._floor
        if op < 0.4:
            mask, cols = cache.lookup(_lanes(u64))
            want_mask, want_vals = model.lookup(u64)
            assert np.array_equal(mask, want_mask)
            for j, c in enumerate(REPORT_COLUMNS):
                assert np.array_equal(cols[c], want_vals[:, j]), c
        elif op < 0.85:
            keep = rng.random(len(u64)) < 0.8 if op < 0.6 else None
            got = cache.insert(_lanes(u64), _cols(vals), rows_mask=keep)
            if keep is not None:
                u64, vals = u64[keep], vals[keep]
            assert got == model.insert(u64, vals)
        elif op < 0.95:
            np.savez(path, keys=u64, **_cols(vals))
            assert cache.load(path) == model.load(u64, vals)
        else:
            state = {"keys": u64, "vals": vals, "hits": step,
                     "misses": 2 * step, "evictions": 3 * step}
            cache.import_state(state)
            model.import_state(u64, vals, step, 2 * step, 3 * step)
        rebuilds += cache._floor != floor
        _assert_same(cache, model)
    if max_rows is not None:
        assert model.evictions > 0 and rebuilds > 0   # both paths ran


def test_index_collisions_stay_distinct(tmp_path):
    """Keys that share the first 64-bit word, or the low slot bits, are
    different entries through lookup, insert, compaction and a save /
    load round trip."""
    x, z = np.uint64(0x1234_5678_9ABC_DEF0), np.uint64(0x0FED_CBA9_8765_4321)
    a, b = (x, np.uint64(1)), (x, np.uint64(2))               # first word
    c, d = (z, np.uint64(3)), (z ^ np.uint64(1 << 50), np.uint64(3))  # slot
    quad = np.array([a, b, c, d], dtype=np.uint64)
    vals = np.arange(16, dtype=np.float64).reshape(4, 4) + 1.0
    cache = PersistentSynthesisCache(max_rows=5)
    assert cache.insert(_lanes(quad), _cols(vals)) == 4
    mask, cols = cache.lookup(_lanes(quad))
    assert mask.all()
    assert np.array_equal(np.stack([cols[c] for c in REPORT_COLUMNS], -1),
                          vals)
    absent = np.array([(x, np.uint64(9)), (z, np.uint64(4))],
                      dtype=np.uint64)
    assert not cache.lookup(_lanes(absent))[0].any()

    # compaction drops all four; re-inserting a and c leaves b and d out
    filler = np.array([(1, 1), (2, 2)], dtype=np.uint64)
    cache.insert(_lanes(filler), _cols(np.ones((2, 4))))
    assert len(cache) == 2 and not cache.lookup(_lanes(quad))[0].any()
    cache.insert(_lanes(quad[[0, 2]]), _cols(vals[[0, 2]]))
    mask, cols = cache.lookup(_lanes(quad))
    assert mask.tolist() == [True, False, True, False]
    assert cols["area_mm2"][[0, 2]].tolist() == [1.0, 9.0]

    # round trip: a fresh cache holding b and d merges a and c as new keys
    path = tmp_path / "synth.npz"
    cache.save(path)
    other = PersistentSynthesisCache()
    other.insert(_lanes(quad[[1, 3]]), _cols(vals[[1, 3]]))
    assert other.load(path) == 4
    mask, cols = other.lookup(_lanes(quad))
    assert mask.all()
    assert np.array_equal(np.stack([cols[c] for c in REPORT_COLUMNS], -1),
                          vals)


def test_index_one_home_slot_batch():
    """A batch whose keys all share one home slot, some repeated: each
    distinct key lands once, each lookup finds its newest row."""
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 2**63, size=(40, 2), dtype=np.uint64)
    keys[:, 0] = (keys[:, 0] << np.uint64(32)) | np.uint64(77)
    batch = keys[rng.integers(0, 40, size=200)]
    vals = np.arange(200 * 4, dtype=np.float64).reshape(200, 4)
    cache, model = PersistentSynthesisCache(), _DictCache(None)
    assert cache.insert(_lanes(batch), _cols(vals)) == \
        model.insert(batch, vals) == len(np.unique(batch, axis=0))
    mask, cols = cache.lookup(_lanes(keys))
    want_mask, want_vals = model.lookup(keys)
    assert np.array_equal(mask, want_mask)
    for j, c in enumerate(REPORT_COLUMNS):
        assert np.array_equal(cols[c], want_vals[:, j])
