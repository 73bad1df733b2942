"""Kernels of the main path compiled for the chip without the chip: the
TPU compiler is installed here and compiles for a described v5e, so what
it refuses, and what it would hold in HBM, is known before a chip run.
Nothing runs on the chip: no result, no time (the plan cases run one
statement over one batch on the CPU, to be handed the plan).  The join
case compiles for about two minutes (four wide sorts), Q3's group
index for about 45 s (two), Q3's whole plan for about a minute and the
decimal Q1 for about 20 s; the rest take seconds.

The topology is described inside a fixture, never at import or
collection: only the worker that is handed this file loads the TPU's
library.  Keep every such test in this one file.
"""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - whatever keeps libtpu out
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _dict_space_compiled(one_chip, batches, cap, dp, nseg):
    import jax
    import jax.numpy as jnp

    from snappydata_tpu.ops import code_agg

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    return jax.jit(
        lambda c, d, g, w: code_agg.dict_space_sum(c, d, g, w, nseg)
    ).lower(shape((batches, cap), jnp.uint8),
            shape((batches, dp), jnp.float32),
            shape((batches * cap,), jnp.int32),
            shape((batches * cap,), jnp.bool_)).compile()


@pytest.mark.parametrize("dp", [16, 64, 256])
def test_dict_space_count_transient_does_not_grow_with_batches(one_chip,
                                                               dp):
    """Q1's shape at SF 2 (96 batches of 131,072 rows, 8 groups) and at
    the documented SF 16 (768): the compiled count step holds no
    scatter, and its temporaries stay inside the chunk budget and do
    not grow with the batch count (materialised one-hots would be
    1.8 GB and 14.5 GB at 64 codes; a masked copy of the group indexes
    0.05 and 0.4 GB)."""
    from snappydata_tpu.ops import code_agg

    cap, nseg = 131072, 9
    temps = []
    for batches in (96, 768):
        comp = _dict_space_compiled(one_chip, batches, cap, dp, nseg)
        assert " scatter(" not in comp.as_text()
        temps.append(comp.memory_analysis().temp_size_in_bytes)
    # each step slices its own rows, so nothing is sized by the table
    assert max(temps) <= code_agg.DICT_SPACE_CHUNK_BYTES, temps
    assert temps[1] <= temps[0] + (1 << 20), temps


def _plan_compiled_at_sf2(monkeypatch, one_chip, sql, load=None):
    """The statement's main (or single) phase as the engine itself
    builds it (float32 plates, the lanes on, TPC-H's dictionary widths),
    compiled for the described chip with every plate at SF 2's shape:
    96 batches of 131,072 rows.  The plan is traced here over one batch
    of SF 0.002; only the shapes are SF 2's.  `load(session)` makes the
    tables in place of the in-tree TPC-H loader."""
    import jax
    import jax.numpy as jnp

    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.engine.executor import CompiledPlan
    from snappydata_tpu.utils import tpch

    seen = []
    orig = CompiledPlan._noted_call

    def spy(self, static, phase, fn, args):
        seen.append((phase, fn, args))
        return orig(self, static, phase, fn, args)

    props = config.global_properties()
    saved = (props.decimal_as_float64, props.get("agg_on_codes"))
    try:
        props.decimal_as_float64 = False
        props.set("agg_on_codes", "on")
        s = SnappySession(catalog=Catalog())
        (load or (lambda s: tpch.load_tpch(s, sf=0.002, seed=11)))(s)
        monkeypatch.setattr(CompiledPlan, "_noted_call", spy)
        s.sql(sql).rows()
        monkeypatch.setattr(CompiledPlan, "_noted_call", orig)
        s.stop()
    finally:
        props.decimal_as_float64 = saved[0]
        props.set("agg_on_codes", saved[1])
    _phase, fn, args = [x for x in seen if x[0] in ("main", "single")][-1]
    batches = 96

    def sds(a, dims):
        return jax.ShapeDtypeStruct(tuple(dims), a.dtype, sharding=one_chip)

    def scalar(a):
        a = jnp.asarray(a)
        return sds(a, a.shape)

    shapes = [jax.tree.map(lambda a: sds(a, (batches,) + a.shape[1:]),
                           args[0]),
              jax.tree.map(scalar, args[1]), jax.tree.map(scalar, args[2])]
    if len(args) == 4:      # phase A's outputs are flat over the rows
        shapes.append(jax.tree.map(
            lambda a: sds(a, (batches * a.shape[0],) + a.shape[1:]
                          if a.ndim else ()), args[3]))
    return fn.lower(*shapes).compile().as_text()


@pytest.mark.parametrize("label", ["q6", "q1"])
def test_plans_at_sf2_hold_no_gather_under_dict_gather(monkeypatch,
                                                       one_chip, label):
    """Q6 and Q1 `main` as the v5e's compiler lowers them at SF 2's batch
    count: the step named `dict_gather` is in the module and no `gather`
    op is under it; with the constant at 0 (the control) one is."""
    from snappydata_tpu.storage import device_decode
    from snappydata_tpu.utils import tpch

    def gathers(hlo):
        # the TPU's fused gather keeps the scope on the fusion's line
        # (kind=kCustom), not on the `gather` inside it: read op names
        return re.findall(r'op_name="[^"]*/dict_gather/[^"]*gather"', hlo)

    sql = {"q1": tpch.Q1, "q6": tpch.Q6}[label]
    hlo = _plan_compiled_at_sf2(monkeypatch, one_chip, sql)
    assert "/dict_gather/" in hlo
    assert gathers(hlo) == []
    monkeypatch.setattr(device_decode, "DICT_SELECT_MAX_WIDTH", 0)
    assert gathers(_plan_compiled_at_sf2(monkeypatch, one_chip, sql))


def test_q3_merge_probes_at_sf1_hold_no_loop_and_share_their_sorts(one_chip):
    """Both of Q3's probes at SF 1 (6,291,456 probe slots against the
    filtered orders build of 1,572,864 and the customer build of
    262,144) as the chip lowers them since PR 28, in one module as the
    plan holds them: no `while`, no gather, no scatter; two sorts a
    join, and because both merged lists pad to one bucket (8,388,608)
    the compiler builds each kind of sort once.  Temporaries stay under
    16 bytes a merged element."""
    import jax
    import jax.numpy as jnp

    from snappydata_tpu.ops import join as dj

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    probe, orders, customer = (48, 131072), 1572864, 262144
    assert dj.probe_lowering("tpu", 48 * 131072, orders) == dj.PROBE_MERGE
    assert dj.probe_lowering("tpu", 48 * 131072, customer) == dj.PROBE_MERGE
    assert dj.expand_bucket(48 * 131072 + orders) \
        == dj.expand_bucket(48 * 131072 + customer) == 8388608

    def both(s1, o1, pass1, k1, s2, o2, k2):
        return (dj.merge_unique(s1, o1, pass1, k1),
                dj.merge_unique(s2, o2, None, k2))

    comp = jax.jit(both).lower(
        shape((orders,), jnp.int64), shape((orders,), jnp.int64),
        shape((orders,), jnp.bool_), shape(probe, jnp.int64),
        shape((customer,), jnp.int64), shape((customer,), jnp.int64),
        shape(probe, jnp.int64)).compile()
    hlo = comp.as_text()
    for op in (" while(", " gather(", " scatter("):
        assert op not in hlo, op
    assert hlo.count(" sort(") == 4
    assert comp.memory_analysis().temp_size_in_bytes <= 16 * 8388608


def _quickstart_main_compiled(monkeypatch, one_chip, strategy, buckets):
    """`select sym, avg(id) ... group by sym`, `main` phase as the chip's
    branch builds it (the backend steered here, in the test, through the
    lowering too: it re-traces), compiled for the described chip at each
    batch bucket of `buckets` (the quick-start cell's is 768: 763
    batches, 128 group slots)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.engine.executor import CompiledPlan

    seen = []
    orig = CompiledPlan._noted_call

    def spy(self, static, phase, fn, args):
        seen.append((phase, fn, args))
        return orig(self, static, phase, fn, args)

    def sds(a, dims):
        return jax.ShapeDtypeStruct(tuple(dims), a.dtype, sharding=one_chip)

    def scalar(a):
        a = jnp.asarray(a)
        return sds(a, a.shape)

    props = config.global_properties()
    saved = (props.decimal_as_float64, props.agg_reduce_strategy)
    try:
        props.decimal_as_float64 = False
        props.agg_reduce_strategy = strategy
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(CompiledPlan, "_noted_call", spy)
        s = SnappySession(catalog=Catalog())
        s.sql("CREATE TABLE testtable (id BIGINT NOT NULL, "
              "sym VARCHAR(10) NOT NULL) USING column")
        ids = np.arange(131072, dtype=np.int64)
        names = np.array([f"sym{k}" for k in range(100)], dtype=object)
        s.insert_arrays("testtable", [ids, names[ids % 100]])
        rows = s.sql("select sym, avg(id) from testtable "
                     "group by sym").rows()
        s.stop()
        assert len(rows) == 100
        (fn, args), = [(f, a) for ph, f, a in seen if ph == "main"]
        comps = []
        for batches in buckets:
            shapes = [
                jax.tree.map(lambda a: sds(a, (batches,) + a.shape[1:]),
                             args[0]),
                jax.tree.map(scalar, args[1]), jax.tree.map(scalar, args[2]),
                jax.tree.map(lambda a: sds(
                    a, (batches * a.shape[0],) + a.shape[1:] if a.ndim
                    else ()), args[3])]
            comps.append(fn.lower(*shapes).compile())
    finally:
        monkeypatch.undo()
        props.decimal_as_float64, props.agg_reduce_strategy = saved
    return comps


def test_quickstart_main_at_100m_rows_holds_no_scatter(monkeypatch,
                                                       one_chip):
    """Since PR 33 the BIGINT sum and the count mask are one limb
    product: no `scatter` in the module, one loop whose product has the
    one-hot fused into it.  What the temporaries hold is the compiler's
    split of the int64 plate into uint32 halves at the parameter and
    their relayout to the flat rows (12 bytes a row at most, 16 with
    the scatters): that part grows with the batch count, and nothing of
    the walker's does (a step's limbs are 7 MB)."""
    rows = 131072
    for batches, comp in zip((96, 768), _quickstart_main_compiled(
            monkeypatch, one_chip, "auto", (96, 768))):
        hlo = comp.as_text()
        assert " scatter(" not in hlo
        assert "/group_reduce/" in hlo and " while(" in hlo
        assert re.search(r"convolution\(%iota_compare_fusion", hlo), \
            "the one-hot is no longer fused into the product"
        mem = comp.memory_analysis()
        assert mem.temp_size_in_bytes <= 12 * batches * rows + (8 << 20)
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 16e9 / 5


def test_quickstart_main_forced_to_scatter_is_two_scatters(monkeypatch,
                                                           one_chip):
    """The control: with the families forced to `scatter` the BIGINT sum
    is one scatter over a pair of uint32 halves and the count one int32
    scatter, both under `group_reduce` (the plan the cell ran before
    PR 33, 9.06 s a statement)."""
    (comp,) = _quickstart_main_compiled(monkeypatch, one_chip, "scatter",
                                        (768,))
    scatters = [ln for ln in comp.as_text().splitlines()
                if " scatter(" in ln]
    assert len(scatters) == 2
    assert all("/group_reduce/scatter-add" in ln for ln in scatters)
    # what each returns, left of the op: s32[128], and (u32[128], u32[128])
    assert sorted(ln.split(" scatter(")[0].count("u32[128]")
                  for ln in scatters) == [0, 2]
    mem = comp.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9 / 5


def test_q3_group_index_at_sf1_is_two_sorts_and_no_loop(one_chip):
    """Q3's generic group index over its 6,291,456 slots at SF 1 (48
    batches of 131,072) as the chip lowers it: the keys' sort, a
    prefix sum over the run heads and the sort that brings the
    ids home; no `while` (the `searchsorted` it replaced was one of 17
    steps), no gather, no scatter (`jnp.unique`'s compaction was one).
    Temporaries stay under 8 bytes a slot."""
    import jax
    import jax.numpy as jnp

    from snappydata_tpu.engine.executor import _run_head_index

    n = 48 * 131072

    def shape(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)

    comp = jax.jit(_run_head_index, static_argnums=2).lower(
        shape(jnp.int64), shape(jnp.bool_), 65536).compile()
    hlo = comp.as_text()
    for op in (" while(", " gather(", " scatter("):
        assert op not in hlo, op
    assert hlo.count(" sort(") == 2
    assert comp.memory_analysis().temp_size_in_bytes <= 8 * n


def test_q3_at_sf1_reduces_over_runs_with_no_scatter_and_no_loop(
        monkeypatch, one_chip):
    """Q3's plan as the chip builds it (the backend steered here, in the
    test, through the lowering too: its probes merge and its families
    resolve as on the chip), compiled for the described v5e with every
    relation at SF 1's 48 batches: 6,291,456 slots, 65,536 group slots.
    The float64 sum, the count and the three keys are read from the
    rows' runs in group order, so no `scatter` is left (the parent held
    five: the sum as a pair of float32, the count and three
    `segment_max`), and no `while` (the run starts are a search of the
    65,537 group ids, unrolled; the probes are merges).  The run
    reduce's sort of the group index with its row numbers and the sum
    is one `sort` beside the index's two and the merges'.  The control,
    that a plan of the fast branch still scatters where asked, is
    `test_quickstart_main_forced_to_scatter_is_two_scatters`."""
    import jax
    import jax.numpy as jnp

    from snappydata_tpu import SnappySession, config
    from snappydata_tpu.catalog import Catalog
    from snappydata_tpu.engine.executor import CompiledPlan
    from snappydata_tpu.utils import tpch

    seen = []
    orig = CompiledPlan._noted_call

    def spy(self, static, phase, fn, args):
        seen.append((phase, fn, args))
        return orig(self, static, phase, fn, args)

    def sds(a, dims):
        return jax.ShapeDtypeStruct(tuple(dims), a.dtype, sharding=one_chip)

    def scalar(a):
        a = jnp.asarray(a)
        return sds(a, a.shape)

    props = config.global_properties()
    saved = props.decimal_as_float64
    try:
        props.decimal_as_float64 = False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(CompiledPlan, "_noted_call", spy)
        s = SnappySession(catalog=Catalog())
        tpch.load_tpch(s, sf=0.002, seed=11)
        s.sql(tpch.Q3).rows()
        s.stop()
        (fn, args), = [(f, a) for ph, f, a in seen if ph == "single"]
        comp = fn.lower(
            jax.tree.map(lambda a: sds(a, (48,) + a.shape[1:]), args[0]),
            jax.tree.map(scalar, args[1]),
            jax.tree.map(scalar, args[2])).compile()
    finally:
        monkeypatch.undo()
        props.decimal_as_float64 = saved
    hlo = comp.as_text()
    assert " scatter(" not in hlo
    assert " while(" not in hlo
    assert "/group_reduce/" in hlo and "/group_keys/" in hlo
    # two merges of two sorts each, the index's two, the run reduce's one
    assert hlo.count(" sort(") == 7
    mem = comp.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9 / 5


def test_decimal_q1_at_sf2_holds_no_scatter_and_no_loop(monkeypatch,
                                                        one_chip):
    """TPC-H Q1 over DECIMAL(15,2) money, its `main` phase as the chip's
    backend lowers it (the strategies steered to the TPU's in the test):
    two guarded s64 products a row and the exact int64 sums by unrolled
    masked reductions: no scatter and no loop."""
    import jax

    from snappydata_tpu.utils import tpch

    money = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    cols = ", ".join(f"CAST({c} AS DECIMAL(15,2)) AS {c}"
                     if c in money else c
                     for c in ("l_returnflag", "l_linestatus", "l_shipdate")
                     + money)

    def load(s):
        tpch.load_tpch(s, sf=0.002, seed=11)
        s.sql(f"CREATE TABLE li_dec AS SELECT {cols} FROM lineitem")

    sql = tpch.Q1.replace("FROM lineitem", "FROM li_dec")
    assert sql != tpch.Q1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _plan_compiled_at_sf2(monkeypatch, one_chip, sql, load)
    assert " scatter(" not in hlo and " while(" not in hlo
    assert "s64[" in hlo
