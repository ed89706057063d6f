"""Ask the v5e's compiler, without a chip: every Pallas kernel in
ops/pallas_kernels.py at the shapes chip_smoke.py gives it, the
multi-key sort, the dense aggregate tail at TPC-H Q1's shape, the
group-by of TPC-H Q18's float64 key, and the programs that carry a join
across the four chips of cell `tpch_q5_4chip` (`shuffle_by_key`,
`join_sharded` shuffled and broadcast, `join_count_sharded`), compiled
for a described `v5e:2x2`.

Nothing runs, so this says nothing about results or speed; it catches
what interpret mode cannot (Mosaic refusing an op or a layout, a program
that does not fit HBM, a sort whose compile takes minutes). The topology
is described inside a fixture — never at import — because only one
process may load the TPU library, and every xdist worker imports this
file. All cases live in this one file for the same reason.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bodo_tpu.ops import pallas_kernels as PK
from bodo_tpu.ops import sort as S

# sort_local compiled alone for the described v5e in 33.7 s at one key
# and 32.1 s at six (PR 25, 8-core sandbox, 2M rows; the variadic
# lax.sort it replaced: 69 s at one uint64 key, six keys not finished
# after 280 s). Six keys stay out of this file to keep it short; the
# ceiling is 3x the measurement so a loaded box does not flap it.
SORT_COMPILE_CEILING_S = 100.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _groupby(shape, n, k, c):
    return PK.matmul_groupby_sum, (shape((n,), jnp.int32),
                                   shape((n, c), jnp.float32)), \
        dict(n_slots=k, n_cols=c), 2


def _gather(shape, n, k):
    return PK._matmul_gather_kernel, (shape((n,), jnp.int32),
                                      shape((k,), jnp.int32)), \
        dict(n_slots=k), 2


def _probe(shape, n, t, planes):
    return PK._hash_probe_kernel, (
        shape((n,), jnp.int32), shape((n,), jnp.int32),
        shape((n, planes), jnp.float32), shape((n,), jnp.bool_),
        shape((t, 1 + planes), jnp.float32)), \
        dict(T=t, n_planes=planes, max_rounds=64), 6


def _partition(shape, n, b):
    return PK._partition_rank_kernel, (shape((n,), jnp.int32),
                                       shape((n,), jnp.bool_)), \
        dict(num_buckets=b), 3


def _range(shape, n, s):
    return PK._range_partition_kernel, (
        shape((n, 4), jnp.float32), shape((s, 4), jnp.float32),
        shape((s,), jnp.bool_)), dict(n_spl=s), 2


# (id, builder, args): the shapes the smoke's phases hand each kernel —
# Q1's grouping over SF1 lineitem, one parquet row group of dictionary
# codes, the taxi merge's probe at 2M rows (larger probes close the
# gate), one shard's rows of the 20M-row four-chip shuffle — each
# kernel's widest slot space, and the gather a benchmark cell hands it
KERNELS = [
    ("groupby_sf1_lineitem", _groupby, (6_001_664, 6, 2)),
    ("groupby_max_slots", _groupby, (1 << 20, 4096, 8)),
    ("gather_rowgroup_dict", _gather, (1 << 20, 4)),
    ("gather_max_slots", _gather, (1 << 20, 4096)),
    # tpch_q5's lineitem probing the LUT built on its 757 suppliers, a
    # range of 3,750: the probe side is the fact table since PR 36
    ("gather_q5_lineitem_probe", _gather, (1_500_032, 3750)),
    ("probe_taxi_2m", _probe, (2_000_000, 512, 4)),
    ("probe_max_slots", _probe, (1 << 20, 4096, 8)),
    ("partition_4_shards", _partition, (5_000_064, 4)),
    ("partition_max_buckets", _partition, (1 << 20, 4096)),
    ("range_4_shards", _range, (5_000_064, 3)),
    ("range_max_splitters", _range, (1 << 20, 4096)),
]


@pytest.mark.parametrize("build,args", [k[1:] for k in KERNELS],
                         ids=[k[0] for k in KERNELS])
def test_kernel_compiles_for_v5e(shape, build, args):
    fn, shapes, static, n_padded_operands = build(shape, *args)
    n = args[0]
    assert PK._rows_fit(n, n_padded_operands), "shape is outside the gate"
    compiled = fn.lower(*shapes, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the gate's arithmetic is the compiler's: [N, 1] operands pad to
    # 512 bytes a row, and that is where the temporaries go
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.25 * n * n_padded_operands * PK._PADDED_ROW_BYTES \
        + (64 << 20), f"{temp >> 20} MiB of temporaries"
    assert temp <= PK._PADDED_BUDGET


def test_sort_local_compiles_for_v5e(shape):
    """One int64 key and a float payload at 2M rows. The sort is one
    (uint32, int32) `lax.sort` in a loop whatever the key list, so its
    compile time does not grow with the keys."""
    n, num_keys = 2_000_000, 1
    arrays = ((shape((n,), jnp.int64), None),
              (shape((n,), jnp.float64), None))
    fn = jax.jit(S.sort_local.__wrapped__,
                 static_argnames=("num_keys", "ascending", "na_last"))
    t0 = time.perf_counter()
    compiled = fn.lower(arrays, shape((), jnp.int64), num_keys=num_keys,
                        ascending=(True,) * num_keys).compile()
    took = time.perf_counter() - t0
    assert took < SORT_COMPILE_CEILING_S, f"{took:.0f}s to compile"
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_sort_local_float64_key_compiles_for_v5e(shape):
    """The TPU compiler cannot bitcast float64 to integer bits (the
    first chip run of PR 25 failed there, in Q5's `order by revenue
    desc`), so a float64 key is compared natively in a pass of its own.
    4096 rows: below the size where that 64-bit sort takes 87 s."""
    n = 4096
    arrays = ((shape((n,), jnp.float64), None),
              (shape((n,), jnp.int32), None))
    fn = jax.jit(S.sort_local.__wrapped__,
                 static_argnames=("num_keys", "ascending", "na_last"))
    fn.lower(arrays, shape((), jnp.int64), num_keys=2,
             ascending=(False, True)).compile()


def test_dense_reduce_tail_compiles_for_v5e(shape):
    """The benchmark's Q1-shaped dense tail (3,000,064 rows, six slots,
    Q1's eight specs over float64) takes the reduce route: the compiler
    accepts it, no scatter in its optimized HLO works on the rows (the
    only ones left compact the six slots), and its temporaries stay
    hundreds of MiB at any slot count: nothing of [rows, n_slots] is
    materialised."""
    from bodo_tpu import relational as R
    n = 3_000_064
    specs = ("sumnull",) * 4 + ("mean",) * 3 + ("size",)
    vn = [f"v{i}" for i in range(8)]
    tree = {"k": (shape((n,), jnp.int32), None),
            "v7": (shape((n,), jnp.int64), None)}
    for c in vn[:7]:
        tree[c] = (shape((n,), jnp.float64), None)

    def compiled(n_slots):
        assert R.dense_route(n_slots, specs, False) == "reduce"

        def body(tree, live):
            return R.dense_agg_tail(tree, live, ["k"], vn, specs,
                                    (n_slots,), (0,), n_slots, False)
        return jax.jit(body).lower(tree, shape((n,), jnp.bool_)).compile()

    six = compiled(6)
    text = six.as_text()
    assert " reduce(" in text
    for computation in text.split("\n\n"):
        if " scatter(" in computation:
            assert str(n) not in computation, computation[:400]
    # both routes hold the float64 columns' float32 halves and a
    # slot-or-none column a null mask, 150 MiB at any slot count: more
    # than rows x 6 x 8, so the bound that shows nothing of
    # [rows, n_slots] was materialised is read at the widest slot space
    # the route takes, where that product is 23 GiB
    temp = six.memory_analysis().temp_size_in_bytes
    assert temp < (256 << 20), f"{temp >> 20} MiB of temporaries"
    widest = R.DENSE_REDUCE_MAX_SLOTS
    temp = compiled(widest).memory_analysis().temp_size_in_bytes
    assert temp < (512 << 20) < n * widest * 8 // 32, \
        f"{temp >> 20} MiB of temporaries at {widest} slots"


def test_float64_group_key_compiles_for_v5e(shape):
    """TPC-H Q18's last group-by: a float64 key (`o_totalprice`) beside
    int64 ones and a string's codes. The hashed route's codes are the
    key's bits, a bitcast the TPU compiler refuses for float64, so
    `relational.groupby_agg` sends such a key list to the sort route,
    whose program compiles. Both halves are asked here: the route the
    gate takes (on this CPU, the same on every platform), and the two
    programs against the described chip."""
    import numpy as np
    import pandas as pd

    from bodo_tpu import Table
    from bodo_tpu import relational as R
    from bodo_tpu.ops import groupby as G
    from bodo_tpu.plan import fusion

    df = pd.DataFrame({"k": np.arange(40, dtype=np.int64) % 5,
                       "p": (np.arange(40) % 3) * 0.25,
                       "v": np.arange(40.0)})
    before = fusion.stats()
    R.groupby_agg(Table.from_pandas(df), ["k", "p"], [("v", "sum", "s")])
    after = fusion.stats()
    assert after["groupby_sort"] == before["groupby_sort"] + 1
    assert after["groupby_hashed"] == before["groupby_hashed"]

    n = 512     # 57 large orders of seven lines, rounded to 128
    keys = ((shape((n,), jnp.int32), None), (shape((n,), jnp.int64), None),
            (shape((n,), jnp.int64), None), (shape((n,), jnp.int64), None),
            (shape((n,), jnp.float64), None))
    arrays = keys + ((shape((n,), jnp.float64), None),)
    fn = jax.jit(G.groupby_local.__wrapped__,
                 static_argnames=("specs", "out_capacity", "num_keys"))
    fn.lower(arrays, shape((), jnp.int64), specs=("sumnull",),
             out_capacity=n, num_keys=len(keys)).compile()
    # what the gate keeps a float64 key away from
    claim = jax.jit(G._groupby_hashed_claim.__wrapped__)
    with pytest.raises(Exception, match="X64 element types"):
        claim.lower(keys, shape((), jnp.int64)).compile()


# ------------------------------------------- a join across the four chips
# `relational.py`'s shard_map programs at a shard's shapes of the two
# top steps of `tpch_4w`'s ladder (6,000,000 and 3,000,000 orders over
# four chips), int64 keys. Seconds are this sandbox's with the file run
# alone (PR 37); a ceiling is about three times its reading.
ORDERS_TOP, ORDERS_NEXT = 6_000_000, 3_000_000


# shuffle_by_key: 50.7 s on the pair, 13.0 s on one key; join_sharded
# after a shuffle: 120.2 s on the pair, 18.3 s on one key (its count 9.5
# and 5.7 s); with a replicated build at 6.0M and 3.0M probe rows a
# shard: 31.8 and 30.3 s (its count 9.2 s). A second 64-bit key costs
# four to six times the compile of one.
SHUFFLE_CEILING_S = 150.0
JOIN_SHUFFLED_CEILING_S = 360.0
JOIN_BROADCAST_CEILING_S = 100.0


def _cap(rows):
    from bodo_tpu.table.table import round_capacity
    return round_capacity(-(-rows // 4))


@pytest.fixture(scope="module")
def four_chips(topo):
    """(mesh key, shapes of row-sharded and replicated arrays) on the
    four described devices; Pallas gates open as on the chip, since the
    described device is not `jax.devices()`."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bodo_tpu import relational as R
    from bodo_tpu.config import config
    mesh = Mesh(np.array(topo.devices), (config.data_axis,))
    row = NamedSharding(mesh, P(config.data_axis))
    rep = NamedSharding(mesh, P())

    def sharded(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=row)

    def replicated(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=rep)
    old = PK.use_pallas
    PK.use_pallas = lambda: True
    yield R._mesh_key(mesh), sharded, replicated
    PK.use_pallas = old


def _timed(lowered, ceiling):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    took = time.perf_counter() - t0
    assert took < ceiling, f"{took:.0f}s to compile"
    return compiled


def _keyed(make, rows, keys, payload):
    """`keys` int64 key columns first, then float64 payload columns."""
    return tuple((make(rows, jnp.int64), None) for _ in range(keys)) + \
        tuple((make(rows, jnp.float64), None) for _ in range(payload))


# a shard of the sharded side of Q5's last join (0.123 rows an order)
# and of the side it meets (0.10): the pair at the top step, one key at
# the next
LAST_JOIN = [("pair_top", 2, _cap(int(0.123 * ORDERS_TOP)),
              _cap(int(0.10 * ORDERS_TOP))),
             ("one_key_next", 1, _cap(int(0.123 * ORDERS_NEXT)),
              _cap(int(0.10 * ORDERS_NEXT)))]


@pytest.mark.parametrize("keys,cap", [c[1:3] for c in LAST_JOIN],
                         ids=[c[0] for c in LAST_JOIN])
def test_shuffle_by_key_compiles_for_four_v5e(four_chips, keys, cap):
    """Hash of the 64-bit keys, `partition_rank`, the `all_to_all` of
    four buckets of a shard's capacity, the compaction of what came."""
    from bodo_tpu import relational as R
    mesh_key, sharded, _ = four_chips
    fn = R._build_shuffle_fn(mesh_key, keys, cap, ("chip_compile", cap),
                             (False,) * 4)
    compiled = _timed(fn.lower(_keyed(sharded, 4 * cap, keys, 4 - keys),
                               sharded(4, jnp.int64)), SHUFFLE_CEILING_S)
    text = compiled.as_text()
    assert "all-to-all" in text and "tpu_custom_call" in text
    # four buckets of `cap` rows a column, sent and received
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


@pytest.mark.parametrize("keys,cap,bcap", [c[1:] for c in LAST_JOIN],
                         ids=[c[0] for c in LAST_JOIN])
def test_join_sharded_compiles_for_four_v5e(four_chips, keys, cap, bcap):
    """Both sides co-located by the shuffle: `join_local` under
    `shard_map`, and the exact count its overflow retry asks for."""
    from bodo_tpu import relational as R
    mesh_key, sharded, _ = four_chips
    args = (_keyed(sharded, 4 * cap, keys, 2),
            _keyed(sharded, 4 * bcap, keys, 1),
            sharded(4, jnp.int64), sharded(4, jnp.int64))
    sig = ("chip_compile", cap, bcap)
    fn = R._build_join_sharded_fn(mesh_key, keys, "inner", 2 * cap, False,
                                  sig, True, "hash")
    compiled = _timed(fn.lower(*args), JOIN_SHUFFLED_CEILING_S)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)
    cfn = R._build_join_count_sharded_fn(mesh_key, keys, "inner", False,
                                         sig, True, "hash")
    _timed(cfn.lower(*args), JOIN_SHUFFLED_CEILING_S)


# a shard of `lineitem` (four lines an order) under the region's
# suppliers (a fifth of `orders // 100`), replicated: Q5's large join
BROADCAST = [("lineitem_top", _cap(4 * ORDERS_TOP),
              _cap(4 * (ORDERS_TOP // 500))),
             ("lineitem_next", _cap(4 * ORDERS_NEXT),
              _cap(4 * (ORDERS_NEXT // 500)))]


@pytest.mark.parametrize("cap,brows", [c[1:] for c in BROADCAST],
                         ids=[c[0] for c in BROADCAST])
def test_join_broadcast_compiles_for_four_v5e(four_chips, cap, brows):
    from bodo_tpu import relational as R
    mesh_key, sharded, replicated = four_chips
    args = (_keyed(sharded, 4 * cap, 1, 2), _keyed(replicated, brows, 1, 1),
            sharded(4, jnp.int64), replicated(1, jnp.int64))
    sig = ("chip_compile", cap, brows)
    fn = R._build_join_sharded_fn(mesh_key, 1, "inner", 2 * cap, True, sig,
                                  True, "hash")
    compiled = _timed(fn.lower(*args), JOIN_BROADCAST_CEILING_S)
    # 6M probe rows a shard: the output at twice the probe's capacity is
    # the largest thing the program holds
    assert compiled.memory_analysis().temp_size_in_bytes < (2 << 30)
    cfn = R._build_join_count_sharded_fn(mesh_key, 1, "inner", True, sig,
                                         True, "hash")
    _timed(cfn.lower(*args), JOIN_BROADCAST_CEILING_S)


def test_sort_sharded_float64_key_compiles_for_four_v5e(four_chips):
    """Q5 ends in `order by revenue desc` over the aggregate's five
    rows, which are sharded on four chips: the sample sort's partition
    key was the float64's bits, a bitcast the TPU compiler refuses (the
    first four-chip run of PR 37 failed there, in its first query); it
    is the key rounded to float32 now."""
    from bodo_tpu.ops import sort as S4
    mesh_key, sharded, _ = four_chips
    cap = 128
    arrays = ((sharded(4 * cap, jnp.float64), None),
              (sharded(4 * cap, jnp.int32), None))
    fn = S4._build_sort_sharded(mesh_key, 2, 1, (False,), True, cap)
    fn.lower(arrays, sharded(4, jnp.int64)).compile()
