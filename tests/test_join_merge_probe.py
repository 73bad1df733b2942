"""The join's probe as one sort-merge (PR 28, ops/join.py): the rank
helper against `np.searchsorted`, the unique-build shortcut against the
loop form's `match_ranges` + `nth_match`, the resolver's table, every
`how` through the engine with the resolver patched to the merge (the CPU
backend keeps the loops on its own), and Q3's compiled HLO without a
`while` under `join_probe`.  Values and counts, never a device time.
"""

import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from snappydata_tpu import SnappySession, config
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.observability import tracing
from snappydata_tpu.observability.metrics import global_registry
from snappydata_tpu.ops import join as dj

I64 = np.iinfo(np.int64)


@pytest.fixture()
def merge_everywhere(monkeypatch):
    """What a TPU resolves at Q3's shapes, on the CPU backend: tests
    steer the resolver here, the program has no switch for it."""
    monkeypatch.setattr(dj, "probe_lowering",
                        lambda backend, n_probe, n_build: dj.PROBE_MERGE)


# ---- the rank helper ------------------------------------------------------

def _bits(values, dtype):
    return np.asarray(dj.key_bits(jnp.asarray(np.asarray(values, dtype))))


_SENTINEL_BUILD = np.sort(np.array(
    [3, 5, 5, 9] + [dj.BUILD_NULL_SENTINEL] * 3, np.int64))

RANK_CASES = {
    "duplicates_on_both_sides": (
        np.sort(np.array([1, 1, 1, 4, 4, 7, 9, 9], np.int64)),
        np.array([0, 1, 1, 2, 4, 7, 7, 8, 9, 10, 4], np.int64)),
    "negative_and_extreme_int64": (
        np.sort(np.array([I64.min, I64.min + 1, -5, -5, 0, 5, I64.max - 9,
                          I64.max - 8], np.int64)),
        np.array([I64.min, -6, -5, -4, 0, 1, I64.max - 9, I64.max - 10,
                  I64.min + 1, I64.min + 2], np.int64)),
    "float_keys_through_key_bits": (
        np.sort(_bits([-2.5, -0.0, 0.0, 0.5, 2.1, 2.9, 1e300], np.float64)),
        _bits([0.0, -0.0, 2.1, 2.9, 2.5, -2.5, 1e300, 3.0], np.float64)),
    "float32_keys_through_key_bits": (
        np.sort(_bits([0.5, 1.5, 1.5, 2.25], np.float32)),
        _bits([1.5, 0.5, 2.0, 2.25, 0.0], np.float32)),
    "one_build_key": (np.array([7], np.int64),
                      np.array([6, 7, 8, 7], np.int64)),
    "one_probe_key": (np.sort(np.array([2, 4, 4, 6], np.int64)),
                      np.array([4], np.int64)),
    "one_and_one": (np.array([3], np.int64), np.array([3], np.int64)),
    "every_probe_below_the_build": (
        np.array([10, 11, 12], np.int64), np.array([1, 2, 3, -7], np.int64)),
    "every_probe_above_the_build": (
        np.array([10, 11, 12], np.int64), np.array([13, 99, 14], np.int64)),
    "build_null_sentinels_at_the_end": (
        _SENTINEL_BUILD, np.array([5, 9, 10, 3, 2], np.int64)),
    "probe_null_sentinels_match_nothing": (
        _SENTINEL_BUILD,
        np.array([dj.PROBE_NULL_SENTINEL, 5, dj.PROBE_NULL_SENTINEL],
                 np.int64)),
    "a_probe_plate_of_two_dimensions": (
        np.sort(np.arange(0, 60, 3).astype(np.int64)),
        np.arange(48, dtype=np.int64).reshape(4, 12)),
}


@pytest.mark.parametrize("case", sorted(RANK_CASES))
def test_merge_rank_equals_numpy_searchsorted(case):
    skeys, qkeys = RANK_CASES[case]
    lo, hi = dj.sorted_rank(jnp.asarray(skeys), jnp.asarray(qkeys),
                            dj.PROBE_MERGE)
    assert lo.shape == hi.shape == qkeys.shape and lo.dtype == jnp.int64
    np.testing.assert_array_equal(
        np.asarray(lo), np.searchsorted(skeys, qkeys, side="left"))
    np.testing.assert_array_equal(
        np.asarray(hi), np.searchsorted(skeys, qkeys, side="right"))
    # and the loop form is the same function
    lo2, hi2 = dj.sorted_rank(jnp.asarray(skeys), jnp.asarray(qkeys),
                              dj.PROBE_LOOP)
    np.testing.assert_array_equal(np.asarray(lo2), np.asarray(lo))
    np.testing.assert_array_equal(np.asarray(hi2), np.asarray(hi))
    if case == "probe_null_sentinels_match_nothing":
        null = qkeys == dj.PROBE_NULL_SENTINEL
        assert (np.asarray(hi - lo)[null] == 0).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_rank_on_random_keys_with_many_ties(seed):
    rng = np.random.default_rng(seed)
    skeys = np.sort(rng.integers(-40, 40, 500).astype(np.int64))
    qkeys = rng.integers(-50, 50, (7, 300)).astype(np.int64)
    lo, hi = dj.sorted_rank(jnp.asarray(skeys), jnp.asarray(qkeys),
                            dj.PROBE_MERGE)
    np.testing.assert_array_equal(np.asarray(lo),
                                  np.searchsorted(skeys, qkeys, "left"))
    np.testing.assert_array_equal(np.asarray(hi),
                                  np.searchsorted(skeys, qkeys, "right"))


# ---- the unique-build shortcut -----------------------------------------------

def _unique_build(rng, n, dead):
    """A unique build as the artifact holds it: flat keys with `dead`
    rows sentineled, their argsort, the sorted keys."""
    keys = rng.permutation(np.arange(-n, n, 2))[:n].astype(np.int64) * 977
    keys[rng.choice(n, dead, replace=False)] = dj.BUILD_NULL_SENTINEL
    order = np.argsort(keys, kind="stable").astype(np.int64)
    return keys, order, keys[order]


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["dense", "build_filter"])
@pytest.mark.parametrize("n_build, n_probe, dead",
                         [(1, 1, 0), (1, 64, 0), (257, 1, 3),
                          (300, 2048, 0), (4096, 512, 100)])
def test_unique_shortcut_equals_ranges_and_nth_match(filtered, n_build,
                                                     n_probe, dead):
    rng = np.random.default_rng(n_build * 31 + n_probe + filtered)
    keys, order, skeys = _unique_build(rng, n_build, dead)
    live = keys != dj.BUILD_NULL_SENTINEL
    pkeys = np.where(rng.random(n_probe) < 0.6,
                     rng.choice(keys[live], n_probe),
                     rng.integers(-n_build, n_build, n_probe) * 977 + 1)
    pkeys[3::7] = dj.PROBE_NULL_SENTINEL
    pkeys = pkeys.astype(np.int64).reshape(1, -1)
    pass_flat = live & (rng.random(n_build) < 0.5) if filtered else live
    js, jo, jp = jnp.asarray(skeys), jnp.asarray(order), jnp.asarray(pkeys)
    if filtered:
        counts, base, cum = dj.match_ranges(js, jo, jnp.asarray(pass_flat),
                                            jp, dj.PROBE_LOOP)
        want_pos = dj.nth_match(base, jnp.int64(0), cum, jo, dj.PROBE_LOOP)
        pass_sorted = jnp.asarray(pass_flat[order])
    else:
        counts, base = dj.match_ranges_dense(js, jp, dj.PROBE_LOOP)
        want_pos = dj.nth_match_dense(base, jnp.int64(0), jo)
        pass_sorted = None
    want = np.asarray(counts) > 0
    found, bpos = dj.merge_unique(js, jo, pass_sorted, jp)
    assert found.shape == bpos.shape == pkeys.shape
    np.testing.assert_array_equal(np.asarray(found), want)
    np.testing.assert_array_equal(np.asarray(bpos)[want],
                                  np.asarray(want_pos)[want])
    # every row found has the probe's key and passes
    assert (keys[np.asarray(bpos)[want]] == pkeys[want]).all()
    assert pass_flat[np.asarray(bpos)[want]].all()
    if n_build > 1 and n_probe >= 64:
        assert want.any() and not want.all()


@pytest.mark.parametrize("seed", [3, 4])
def test_pass_aware_ranges_and_nth_match_merge_equals_loop(seed):
    """Duplicate build keys under a build filter: counts, bases and the
    k-th passing row of every range, merge against loop."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 25, 400).astype(np.int64)
    keys[rng.choice(400, 30, replace=False)] = dj.BUILD_NULL_SENTINEL
    order = np.argsort(keys, kind="stable").astype(np.int64)
    pass_flat = (keys != dj.BUILD_NULL_SENTINEL) & (rng.random(400) < 0.6)
    pkeys = rng.integers(-3, 28, (3, 50)).astype(np.int64)
    args = (jnp.asarray(keys[order]), jnp.asarray(order),
            jnp.asarray(pass_flat), jnp.asarray(pkeys))
    c0, b0, cum0 = dj.match_ranges(*args, dj.PROBE_LOOP)
    c1, b1, cum1 = dj.match_ranges(*args, dj.PROBE_MERGE)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b0))
    want = np.array([[(pass_flat & (keys == k)).sum() for k in row]
                     for row in pkeys])
    np.testing.assert_array_equal(np.asarray(c1), want)
    for k in range(int(want.max())):
        has = want > k
        p0 = dj.nth_match(b0, jnp.int64(k), cum0, args[1], dj.PROBE_LOOP)
        p1 = dj.nth_match(b1, jnp.int64(k), cum1, args[1], dj.PROBE_MERGE)
        np.testing.assert_array_equal(np.asarray(p1)[has],
                                      np.asarray(p0)[has])


# ---- the resolver -------------------------------------------------------------

@pytest.mark.parametrize("backend, n_probe, n_build, want", [
    # Q3 at SF 1 on the chip: both joins
    ("tpu", 6291456, 1572864, "merge"),
    ("tpu", 6291456, 262144, "merge"),
    # the CPU backend (every tier-1 test), whatever the shapes
    ("cpu", 6291456, 1572864, "loop"),
    ("cpu", 1, 1, "loop"),
    ("gpu", 6291456, 1572864, "loop"),
    # a probe far smaller than its build gathers less than a sort moves;
    # the sweep on the chip (PERF.md section 6, PR 28) crossed between
    # P/B 1/256 and 1/64 at a build of 4,194,304
    ("tpu", 1024, 1 << 24, "loop"),
    ("tpu", 1 << 14, 1 << 22, "loop"),
    ("tpu", 1 << 16, 1 << 22, "merge"),
    ("tpu", 1 << 20, 1 << 22, "merge"),
    ("tpu", 1 << 22, 1 << 22, "merge"),
    ("tpu", 1 << 22, 1 << 16, "merge"),
    ("tpu", 4096, 4096, "merge"),
    ("tpu", 1, 1, "merge"),
    # past the merged order's int32 tags: the loop, not an error
    ("tpu", 1 << 29, 1 << 29, "loop"),
])
def test_resolver_table(backend, n_probe, n_build, want):
    assert dj.probe_lowering(backend, n_probe, n_build) == want


# ---- every `how` through the engine, merge against the host oracle ---------

def _main_attrs(root):
    def walk(sp):
        yield sp
        for c in sp.get("children", ()):
            yield from walk(c)
    return [sp["attrs"] for sp in walk(root)
            if sp["name"] in ("jit_compile", "device_execute")
            and sp["attrs"].get("phase", "main") == "main"]


def _device_and_host(sess, q):
    p = config.global_properties()
    saved = p.get("device_join")
    f0 = global_registry().counter("join_host_fallbacks")
    dev = sess.sql(q).rows()
    attrs = _main_attrs(tracing.ring().last().to_dict()["root"])
    fallbacks = global_registry().counter("join_host_fallbacks") - f0
    p.set("device_join", False)
    try:
        host = sess.sql(q).rows()
    finally:
        p.set("device_join", saved)
    return dev, host, fallbacks, attrs


def _keys(rng, n, unique):
    if unique:
        return [int(v) for v in rng.permutation(60)[:n]]
    return [None if rng.random() < 0.15 else int(v)
            for v in rng.integers(0, 8, n)]


JOINS = {
    "inner": "SELECT a.lv, b.rv FROM tl a JOIN tr b ON a.k = b.k",
    "left": "SELECT a.lv, b.rv FROM tl a LEFT JOIN tr b ON a.k = b.k",
    "right": "SELECT a.lv, b.rv FROM tl a RIGHT JOIN tr b ON a.k = b.k",
    "full": "SELECT a.lv, b.rv FROM tl a FULL JOIN tr b ON a.k = b.k",
    "inner_build_filter": "SELECT a.lv, b.rv FROM tl a JOIN "
                          "(SELECT * FROM tr WHERE rv % 3 <> 0) b "
                          "ON a.k = b.k",
    "left_build_filter": "SELECT a.lv, b.rv FROM tl a LEFT JOIN "
                         "(SELECT * FROM tr WHERE rv % 3 <> 0) b "
                         "ON a.k = b.k",
    "semi": "SELECT a.lv, a.k FROM tl a WHERE EXISTS "
            "(SELECT 1 FROM tr b WHERE b.k = a.k)",
    "anti": "SELECT a.lv, a.k FROM tl a WHERE NOT EXISTS "
            "(SELECT 1 FROM tr b WHERE b.k = a.k)",
}


@pytest.mark.parametrize("unique", [True, False],
                         ids=["unique_build", "one_to_many"])
@pytest.mark.parametrize("how", sorted(JOINS))
def test_every_how_gives_the_hosts_rows_under_the_merge(
        merge_everywhere, how, unique):
    rng = np.random.default_rng(zlib.crc32(f"{how}/{unique}".encode()))
    props = config.global_properties()
    saved = props.tracing_enabled
    props.tracing_enabled = True
    s = SnappySession(catalog=Catalog())
    try:
        s.sql("CREATE TABLE tl (k BIGINT, lv INT) USING column")
        s.sql("CREATE TABLE tr (k BIGINT, rv INT) USING column")
        for i, k in enumerate(_keys(rng, 41, False)):
            s.insert("tl", (k, i))
        for i, k in enumerate(_keys(rng, 23, unique)):
            s.insert("tr", (k, 1000 + i))
        q = JOINS[how] + " ORDER BY 1 NULLS LAST, 2 NULLS LAST"
        dev, host, fallbacks, attrs = _device_and_host(s, q)
    finally:
        s.stop()
        props.tracing_enabled = saved
    assert fallbacks == 0, "expected the device join"
    assert dev == host and len(host) > 0
    (main,) = attrs
    assert main["join_device_joins"] == 1
    assert main["join_merge_probes"] == 1
    # only the expansion's own search is still a loop
    expands = main["join_expand_out_rows"] > 0
    assert main["join_search_loops"] == (1 if expands else 0)
    if how in ("inner", "left", "inner_build_filter", "left_build_filter"):
        assert expands == (not unique)


# ---- the lowering: Q3's HLO -----------------------------------------------------

def _whiles_under(hlo: str, scope: str):
    return [ln for ln in hlo.splitlines()
            if " while(" in ln and f"/{scope}/" in ln]


def _q3_hlo_and_note(monkeypatch):
    """Compiled HLO of Q3's one dispatch at SF 0.002 and the plan's
    trace-time join note."""
    from snappydata_tpu.engine.executor import CompiledPlan
    from snappydata_tpu.utils import tpch

    seen = []
    orig = CompiledPlan._noted_call

    def spy(self, static, phase, fn, args):
        seen.append((self, static, phase, fn, args))
        return orig(self, static, phase, fn, args)

    s = SnappySession(catalog=Catalog())
    try:
        tpch.load_tpch(s, sf=0.002, seed=11)
        monkeypatch.setattr(CompiledPlan, "_noted_call", spy)
        s.sql(tpch.Q3).rows()
        monkeypatch.setattr(CompiledPlan, "_noted_call", orig)
    finally:
        s.stop()
    plan, static, _phase, fn, args = [
        x for x in seen if x[2] in ("main", "single")][-1]
    return fn.lower(*args).compile().as_text(), plan.join_notes[static]


def test_q3_holds_no_while_under_join_probe_with_the_merge(monkeypatch):
    """The loop form is the control: its `searchsorted` loops are
    `while` ops under `join_probe`; the merge leaves none there and none
    under `join_gather` (the filtered build's third search)."""
    hlo, note = _q3_hlo_and_note(monkeypatch)
    assert note["join_device_joins"] == 2
    assert note["join_merge_probes"] == 0 and note["join_search_loops"] == 6
    assert "/join_probe/" in hlo
    assert len(_whiles_under(hlo, "join_probe")) >= 4

    monkeypatch.setattr(dj, "probe_lowering",
                        lambda backend, n_probe, n_build: dj.PROBE_MERGE)
    hlo, note = _q3_hlo_and_note(monkeypatch)
    assert note["join_device_joins"] == 2
    assert note["join_merge_probes"] == 2 and note["join_search_loops"] == 0
    assert "/join_probe/" in hlo
    assert _whiles_under(hlo, "join_probe") == []
    assert _whiles_under(hlo, "join_gather") == []
    assert any(" sort(" in ln and "/join_probe/" in ln
               for ln in hlo.splitlines())


@pytest.mark.parametrize("merge", [False, True], ids=["loop", "merge"])
def test_q3_reads_no_row_build_and_no_multikey_join(monkeypatch, merge):
    """The counters PR 36 added leave the join cell's program as it was:
    Q3's two builds are column tables matched on one key pair each, and
    the build slots are the two builds' padded slots."""
    from snappydata_tpu.utils import tpch

    if merge:
        monkeypatch.setattr(dj, "probe_lowering",
                            lambda backend, n_probe, n_build: dj.PROBE_MERGE)
    props = config.global_properties()
    saved = props.tracing_enabled
    props.tracing_enabled = True
    s = SnappySession(catalog=Catalog())
    try:
        tpch.load_tpch(s, sf=0.002, seed=11)
        s.sql(tpch.Q3).rows()
        (main,) = _main_attrs(tracing.ring().last().to_dict()["root"])
    finally:
        s.stop()
        props.tracing_enabled = saved
    assert main["join_device_joins"] == 2
    assert main["join_merge_probes"] == (2 if merge else 0)
    assert main["join_row_builds"] == 0
    assert main["join_multikey_joins"] == 0
    assert main["join_build_rows"] > 0
    assert main["join_build_rows"] % 2 == 0
