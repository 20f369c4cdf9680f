"""The main path's kernels, compiled for a v5e that is described and not
attached (``jax.experimental.topologies``): what the chip's compiler
refuses — a 64-bit type inside a Pallas kernel, a slice Mosaic cannot
lower, a program that does not fit — fails here, at no chip time.
Nothing runs; a compile that passes is not a chip run
(``python chip_smoke.py`` through the chip tool is).

The described chip is not the default backend (tier-1 pins the CPU), so
the Pallas tests steer the ONE interpret rule
(``compile.service.pallas_interpret``) from here.  The topology is
described inside a module-scoped fixture — never at import — because
only one process may load libtpu: every xdist worker collects this
file, and only the worker that runs it may touch the library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

CAP = 1 << 20  # spark.rapids.sql.reader.batchSizeRows default


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the Pallas kernels as the chip would: never interpreted."""
    from spark_rapids_tpu.compile import service
    monkeypatch.setattr(service, "pallas_interpret", lambda: False)


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


@pytest.mark.parametrize("K", [128, 1024])
def test_pallas_agg_compiles_for_v5e(one_chip, mosaic, K):
    """Every plane dtype x op ``make_update_body`` emits on the chip:
    int32 add (counts, int64-sum limbs), f32 add (sums), int32/f32
    min/max."""
    from spark_rapids_tpu.exec import pallas_agg
    dtypes = (jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.int32,
              jnp.int32, jnp.int32, jnp.float32, jnp.float32, jnp.int32,
              jnp.int32)
    ops = ("add", "add", "add", "add", "add", "add", "add", "min",
           "max", "min", "max")

    def sds(dt):
        return jax.ShapeDtypeStruct((CAP,), dt, sharding=one_chip)

    compiled = _compile(
        lambda gid, *planes: pallas_agg._pallas_reduce(
            gid, planes, ops, K, CAP),
        sds(jnp.int32), *[sds(dt) for dt in dtypes])
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("radices", [(4, 3), ()],
                         ids=["q1_two_code_keys", "q6_keyless"])
def test_dense_update_compiles_for_v5e(one_chip, mosaic, monkeypatch,
                                       radices):
    """The whole update program of an aggregate nothing was folded
    into, at the scan's batch capacity: one projection of the keys and
    of one input per function, as ``_run_update`` builds it, then the
    mixed-radix slot, q1's sixteen planes (four sums and three averages
    with their counts, a row count) at K = 128, the compaction and the
    per-digit key rebuild; zero digits is q6's keyless shape.  Outputs
    are as long as the domain's bucket, not as the input."""
    import spark_rapids_tpu.exec.aggregate as agg_mod
    from spark_rapids_tpu.columnar import dtypes
    from spark_rapids_tpu.columnar.dtypes import FLOAT64, INT32
    from spark_rapids_tpu.exec import pallas_agg, stage
    from spark_rapids_tpu.exprs import aggregates as agf
    from spark_rapids_tpu.exprs.base import BoundReference, Literal
    monkeypatch.setattr(dtypes, "_DOUBLE_AS_FLOAT", True)
    nk = len(radices)
    keys = [BoundReference(i, INT32, True, f"k{i}") for i in range(nk)]
    vals = [BoundReference(nk + i, FLOAT64, True, f"v{i}")
            for i in range(4)]
    funcs = [(f"s{i}", agf.Sum, v) for i, v in enumerate(vals)]
    funcs += [(f"a{i}", agf.Average, v) for i, v in enumerate(vals[:3])]
    funcs.append(("n", agf.Count, Literal(1, INT32)))
    tail = ("project", tuple(keys) + tuple(e for _, _, e in funcs))
    spec = agg_mod._AggSpec(keys, [
        (n, cls(BoundReference(nk + j, e.dtype, e.nullable, e.name)))
        for j, (n, cls, e) in enumerate(funcs)])
    assert pallas_agg.supports(spec)
    h_steps, values = stage.hoist_steps((tail,))
    sig = tuple([(INT32.name, CAP, 0)] * nk + [(FLOAT64.name, CAP, 0)] * 4)
    agg_mod._AGG_CACHE.clear()
    prog = agg_mod._compile_folded_update(h_steps, sig, (), CAP, spec,
                                          radices)
    agg_mod._AGG_CACHE.clear()
    flat, aux, _n, _pid, hoisted = stage.aval_inputs(sig, CAP, values)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        ((flat,), (aux,), jax.ShapeDtypeStruct((1,), jnp.int32), hoisted,
         jax.ShapeDtypeStruct((1, nk), jnp.int64)))
    lowered = prog.lower(*avals)
    assert "tpu_custom_call" in lowered.compile().as_text()
    ((_n_groups, key_outs, buf_outs, _valid),) = lowered.out_info
    assert len(key_outs) == nk and len(buf_outs) == 15
    out_cap = 16 if radices else 8
    assert {cv.data.shape for cv in key_outs + buf_outs} == {(out_cap,)}
    # the buffers' validity leaves once (an output buffer is what a
    # launch costs the host): a count, two planes a key, one a buffer
    assert all(cv.validity is None for cv in buf_outs)
    assert len(jax.tree_util.tree_leaves(lowered.out_info)) == \
        1 + 2 * nk + 15 + 1


# -- the folded update: q1 and q6 as the planner hands them over ----------

@pytest.fixture(scope="module")
def folded_updates(tmp_path_factory):
    """What ``TpuHashAggregateExec._run_update`` asks
    ``_compile_folded_update`` for while TPC-H q1 and q6 run over a
    dictionary-encoded lineitem (20 k rows, on the CPU): the hoisted
    steps, signatures, spec and radices of the real path, re-aimed by
    the tests below at the scan's batch capacity.  Also the programs the
    two queries launched."""
    import spark_rapids_tpu.exec.aggregate as agg_mod
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.compile import service
    from tests.compare import tpu_session
    real_compile, real_args = (agg_mod._compile_folded_update,
                               agg_mod.hoisted_args)
    asked, values = [], []

    def compile_(*a):
        asked.append(a)
        return real_compile(*a)

    def args_(v):
        values.append(v)
        return real_args(v)

    paths = tpch.gen_tpch(str(tmp_path_factory.mktemp("fold")), 20_000)
    agg_mod._compile_folded_update, agg_mod.hoisted_args = compile_, args_
    before = service.ledger_rows()
    try:
        s = tpu_session({})
        tables = tpch.load_tables(s, {"lineitem": paths["lineitem"]})
        out = {}
        for name in ("q1", "q6"):
            del asked[:], values[:]
            assert tpch.TPCH_QUERIES[name](tables).to_arrow().num_rows
            out[name] = (asked[0], values[0])
        s.stop()
    finally:
        agg_mod._compile_folded_update = real_compile
        agg_mod.hoisted_args = real_args
    after = service.ledger_rows()
    out["launched"] = sorted(
        p for p, row in after.items() if row["dispatches"]
        > before.get(p, {"dispatches": 0})["dispatches"])
    return out


def _folded_at_cap(folded_updates, query, sharding=None, members=1):
    """(program, avals) of ``query``'s folded update over ``members``
    batches of ``CAP`` rows (lineitem at SF1 is six)."""
    import spark_rapids_tpu.exec.aggregate as agg_mod
    from spark_rapids_tpu.exec import stage
    (h_steps, sig, aux_sig, cap, spec, radices, _one), values = \
        folded_updates[query]
    assert radices is not None  # both take the dense body
    sig = tuple((name, CAP if c == cap else c, w) for name, c, w in sig)
    agg_mod._AGG_CACHE.clear()
    prog = agg_mod._compile_folded_update(h_steps, sig, aux_sig, CAP,
                                          spec, radices, members)
    agg_mod._AGG_CACHE.clear()
    flat, aux, _n, _pid, hoisted = stage.aval_inputs(sig, CAP, values,
                                                     aux_sig)
    avals = ((flat,) * members, (aux,) * members,
             jax.ShapeDtypeStruct((members,), jnp.int32), hoisted,
             jax.ShapeDtypeStruct((members, len(radices)), jnp.int64))
    if sharding is not None:
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), avals)
    return prog, avals


def _row_plane_moves(jaxpr) -> list:
    """Gathers and scatters of ``jaxpr``, outside any Pallas call, that
    read, write or index a capacity-long array."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            continue
        if name == "gather" or name.startswith("scatter"):
            shapes = [v.aval.shape for v in eqn.invars]
            if any(CAP in shape for shape in shapes):
                found.append((name, shapes))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_row_plane_moves(sub))
    return found


@pytest.mark.parametrize("members", [1, 6], ids=["one", "group"])
@pytest.mark.parametrize("query", ["q1", "q6"])
def test_folded_update_moves_no_row_plane(folded_updates, query, members):
    """The gathers cannot come back unseen: with the filter folded in,
    what is left outside the Pallas call is elementwise (the predicate,
    the slot, the masked planes) and K-slot work at the end — no gather
    and no scatter over 2^20 rows, in the group's program neither (its
    stack is a concatenation, its map a loop of slices).  And neither
    query launched a ``stage_*`` program."""
    prog, avals = _folded_at_cap(folded_updates, query, members=members)
    jaxpr = prog.trace(*avals).jaxpr
    assert "pallas_call" in str(jaxpr)
    assert _row_plane_moves(jaxpr.jaxpr) == []
    launched = folded_updates["launched"]
    assert "aggregate_masked_pallas_update" in launched
    assert not [p for p in launched if p.startswith("stage_")], launched


def test_a_compacting_filter_is_what_the_guard_would_catch():
    """The guard's own control: the stage compiler's filter, as every
    other consumer still runs it, gathers every plane."""
    from spark_rapids_tpu.columnar.dtypes import FLOAT64
    from spark_rapids_tpu.exec import stage
    from spark_rapids_tpu.exprs.base import BoundReference, Literal
    from spark_rapids_tpu.exprs.predicates import GreaterThan
    steps = (("filter", (GreaterThan(BoundReference(0, FLOAT64, True, "v"),
                                     Literal(1.5, FLOAT64)),)),)
    sig = (("double", CAP, 0),)
    jaxpr = jax.jit(stage._build_stage_fn(steps, CAP)).trace(
        *stage.aval_inputs(sig, CAP, ())).jaxpr
    assert _row_plane_moves(jaxpr.jaxpr)


@pytest.mark.parametrize("members", [1, 6], ids=["one", "group"])
@pytest.mark.parametrize("query", ["q1", "q6"])
def test_folded_update_compiles_for_v5e(one_chip, mosaic, monkeypatch,
                                        folded_updates, query, members):
    """The whole program the chip runs per batch of q1 and q6 since the
    fold — predicate, mixed-radix slot, Mosaic accumulation, K-slot
    tail — at 2^20 rows with the device's f32 doubles, and the program
    that updates all six batches of lineitem at SF1 in one launch (the
    same body under ``lax.map`` over a stack of the members); neither's
    optimized HLO moves a 2^20-long plane through a gather or a
    scatter, and the group's temporaries (the stack, a member's planes)
    stay a small part of the chip."""
    from spark_rapids_tpu.columnar import dtypes
    monkeypatch.setattr(dtypes, "_DOUBLE_AS_FLOAT", True)
    prog, avals = _folded_at_cap(folded_updates, query, one_chip, members)
    compiled = prog.lower(*avals).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    moves = [ln.strip()[:160] for ln in text.splitlines()
             if (" gather(" in ln or " scatter(" in ln)
             and f"{CAP}]" in ln]
    assert moves == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_pallas_agg_refuses_64bit_planes_on_the_chip(mosaic):
    """What ``supports()`` must never admit raises, typed, at trace
    time — it does not run another path."""
    from spark_rapids_tpu.exec import pallas_agg
    with pytest.raises(TypeError, match="no Mosaic lowering"):
        jax.eval_shape(
            lambda g, p: pallas_agg._pallas_reduce(
                g, (p,), ("add",), 128, 1024),
            jax.ShapeDtypeStruct((1024,), jnp.int32),
            jax.ShapeDtypeStruct((1024,), jnp.float64))


def _needle(k: int) -> bytes:
    """All bytes distinct: the worst case for the XLA unroll's fusion."""
    return bytes(range(65, 65 + k))


def _chars_and_lens(one_chip):
    return (jax.ShapeDtypeStruct((CAP, 64), jnp.uint8, sharding=one_chip),
            jax.ShapeDtypeStruct((CAP,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("at_threshold", [True, False])
def test_pallas_contains_compiles_for_v5e(one_chip, mosaic, at_threshold):
    """At the routing threshold and at 16 bytes below it: the kernel
    takes any length, whatever ``functions.contains`` sends it."""
    from spark_rapids_tpu.exprs import pallas_strings
    k = pallas_strings.PALLAS_PATTERN_MIN if at_threshold else 16
    compiled = _compile(
        lambda chars, lens: pallas_strings._run_contains(
            chars, lens, _needle(k)),
        *_chars_and_lens(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fused", [True, False])
def test_contains_threshold_is_where_the_xla_unroll_stops_fusing(
        one_chip, fused):
    """Why ``PALLAS_PATTERN_MIN`` is 24: one byte under it the chip's
    compiler fuses the XLA unroll into a single pass with no HBM temp
    whatever the needle's bytes; from it on a needle of distinct bytes
    has its shifted slices materialised (1.2 GB at this shape, 6 GB at
    width 256; a 128-byte needle there no longer compiles).  If this
    fails after an upgrade, move the threshold to the new limit."""
    from types import SimpleNamespace

    from spark_rapids_tpu.exprs import pallas_strings
    from spark_rapids_tpu.exprs.strings import Contains
    k = pallas_strings.PALLAS_PATTERN_MIN - (1 if fused else 0)
    compiled = _compile(
        lambda chars, lens: Contains._match(
            SimpleNamespace(pat=_needle(k)),
            SimpleNamespace(chars=chars, data=lens, validity=None)),
        *_chars_and_lens(one_chip))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (1 << 20) if fused else temp > (256 << 20), temp


def test_fused_entry_step_compiles_for_v5e(one_chip):
    """``__graft_entry__.entry()``: filter + project + sorted-segment
    aggregate in one XLA program at the default batch capacity."""
    import __graft_entry__ as graft
    step, (flat, num_rows) = graft.entry(CAP)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        (flat, num_rows))
    compiled = _compile(step, *avals)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_sort_permutation_compiles_for_v5e(one_chip):
    from spark_rapids_tpu.exec.sortkeys import sort_permutation

    def sds(dt):
        return jax.ShapeDtypeStruct((CAP,), dt, sharding=one_chip)

    compiled = _compile(
        lambda k0, k1, live: sort_permutation([k0, k1], CAP, live),
        sds(jnp.int64), sds(jnp.float32), sds(jnp.bool_))
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_distributed_aggregate_compiles_for_four_chips(topo):
    """One width-4 ``DistributedAggregate`` step over ``Mesh(topo
    .devices)``: the exchange must lower to an ``all_to_all`` across the
    mesh, and each chip's share must fit beside a real working set."""
    from spark_rapids_tpu.columnar.dtypes import FLOAT32, INT64
    from spark_rapids_tpu.exprs.aggregates import Count, Sum
    from spark_rapids_tpu.exprs.base import Alias, BoundReference
    from spark_rapids_tpu.parallel import DistributedAggregate
    from spark_rapids_tpu.parallel.mesh import DATA_AXIS
    mesh = Mesh(np.asarray(topo.devices), (DATA_AXIS,))
    n_dev, cap = mesh.devices.size, CAP // mesh.devices.size
    assert n_dev == 4
    k = BoundReference(0, INT64, True, "k")
    v = BoundReference(1, FLOAT32, True, "v")
    dist = DistributedAggregate(
        [k], [Alias(Count(v), "cnt"), Alias(Sum(v), "s")], mesh=mesh)
    rows = NamedSharding(mesh, P(DATA_AXIS))

    def plane(dt):
        return jax.ShapeDtypeStruct((n_dev, cap), dt, sharding=rows)

    stacked = ((plane(jnp.int64), plane(jnp.bool_), None),
               (plane(jnp.float32), plane(jnp.bool_), None))
    counts = jax.ShapeDtypeStruct((n_dev,), jnp.int32, sharding=rows)
    from spark_rapids_tpu.parallel.mesh import planes_signature
    compiled = dist._step(cap, planes_signature(stacked)).lower(
        stacked, counts, ()).compile()
    assert "all-to-all" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30
