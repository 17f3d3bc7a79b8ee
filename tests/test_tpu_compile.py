"""Real-size compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached.  These cases compile the main path's
programs at deployment shapes (2^20 points) and so catch what the Pallas
interpreter and the CPU backend never see: scoped-VMEM overflows, tilings
Mosaic refuses, programs that do not fit the device.  Nothing runs, so
they say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import pytest

N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k", [8, 32, 128, 256])
def test_pairwise_topk_compiles_at_2_20_points(one_chip, k):
    """The kernel at the lane-padded chip layout (d=3 padded to 128), with
    the query tile the wrapper derives from k."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.pairwise_topk import pairwise_topk_padded, query_tile

    tq = query_tile(k)

    def fn(q, qid, p, r2):
        return pairwise_topk_padded(
            q, qid, p, r2, k=k, n_real=N, tq=tq, tp=512, metric="l2",
            n_dim=3,
        )

    compiled = jax.jit(fn).lower(
        _shape(one_chip, (512, 128), jnp.float32),
        _shape(one_chip, (512, 1), jnp.int32),
        _shape(one_chip, (N, 128), jnp.float32),
        _shape(one_chip, (1, 1), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grid_round_compiles_at_2_20_points(one_chip):
    """One fixed-radius round over 2^20 points at a LiDAR-like grid
    (table 2^20, cap 32), chunked as the host driver chunks it."""
    import jax.numpy as jnp

    from repro.core.fixed_radius import _round_impl, round_chunk

    d, cap, table, q = 3, 32, 1 << 20, 2048
    chunk = round_chunk(2048, d, cap)
    compiled = _round_impl.lower(
        _shape(one_chip, (table, cap), jnp.int32),
        (_shape(one_chip, (table, cap), jnp.float32),) * d,
        _shape(one_chip, (d,), jnp.float32),
        _shape(one_chip, (d,), jnp.float32),
        _shape(one_chip, (d,), jnp.int32),
        _shape(one_chip, (q, d), jnp.float32),
        _shape(one_chip, (q,), jnp.int32),
        _shape(one_chip, (), jnp.float32),
        n=N, table_size=table, k=8, chunk=chunk,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert _candidate_gathers_are_bucket_rows(compiled.as_text(), "")


def test_grid_binning_compiles_at_2_20_points(one_chip):
    """Grid set-up: the cloud's one bucket sort (table size traced) and the
    per-shape slot fill, at a LiDAR-like shape and a heavy-tailed one."""
    import jax.numpy as jnp

    from repro.core.grid import _bucket_order, _fill_buckets

    d = 3
    _bucket_order.lower(
        _shape(one_chip, (N, d), jnp.float32),
        _shape(one_chip, (d,), jnp.float32),
        _shape(one_chip, (d,), jnp.float32),
        _shape(one_chip, (d,), jnp.int32),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (), jnp.int32),
    ).compile()
    for table, cap in ((1 << 21, 8), (1 << 14, 2048)):
        _fill_buckets.lower(
            _shape(one_chip, (N,), jnp.int32),
            _shape(one_chip, (N,), jnp.int32),
            _shape(one_chip, (N, d), jnp.float32),
            _shape(one_chip, (), jnp.int32),
            table_size=table, cap=cap,
        ).compile()


def test_brute_engine_compiles_at_2_20_points(one_chip):
    """The exact engine behind brute, TrueKNN's tail and Alg. 2 sampling."""
    import jax.numpy as jnp

    from repro.core.brute import _brute_impl

    compiled = _brute_impl.lower(
        _shape(one_chip, (N, 3), jnp.float32),
        _shape(one_chip, (512, 3), jnp.float32),
        _shape(one_chip, (512,), jnp.int32),
        k=8, chunk=512, exclude_self=False, metric="l2",
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_brute_engine_compiles_for_the_sift_cell(one_chip):
    """The dense cell's whole engine: 10^6 descriptors of 128 dimensions,
    10,000 queries padded to 20 chunks of 512, k=10.  Its cross term is a
    float32 (HIGHEST) matmul, and its temp memory is about one (512, 10^6)
    float32 distance block (2.05 GB)."""
    import jax.numpy as jnp

    from repro.core.brute import _brute_impl

    n, d, q = 10**6, 128, 10240
    compiled = _brute_impl.lower(
        _shape(one_chip, (n, d), jnp.float32),
        _shape(one_chip, (q, d), jnp.float32),
        _shape(one_chip, (q,), jnp.int32),
        k=10, chunk=512, exclude_self=False, metric="l2",
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30
    text = compiled.as_text()
    # the TPU compiler writes the matmul as a convolution
    (cross,) = [ln for ln in text.splitlines()
                if "brute.dist/" in ln and "dot_general" in ln
                and ("convolution(" in ln or " dot(" in ln)]
    assert "operand_precision={highest,highest}" in cross


def test_fused_loop_compiles_for_a_kitti_schedule(one_chip):
    """The one-dispatch TrueKNN program for the first rounds of a 2^20-point
    LiDAR schedule (grid shapes as ``build_grid`` sizes them there), one
    512-query serving batch, with the exact brute tail."""
    from repro.core.fused_loop import _fused_fn

    grids = ((1 << 21, 8), (1 << 21, 16), (1 << 20, 32), (1 << 18, 128))
    fn = _fused_fn(
        tuple(t for t, _ in grids), tuple(range(len(grids))), True, 8,
        512, 512,
    )
    compiled = _compile_fused(one_chip, fn, grids, 512)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 << 30
    assert _candidate_gathers_are_bucket_rows(compiled.as_text())


def test_fused_loop_compiles_for_the_frame_cell_schedule(one_chip):
    """The program the benchmark's frame cell runs: 8,192 query rows, the
    five lattice grids ``build_grid`` sizes for the 2^20-point HDL-64E map
    (radii 0.059 to 0.946 m, each at the 2^25-slot bucket budget), 2,048-row
    chunks and the exact brute tail."""
    from repro.core.fused_loop import _fused_fn

    grids = ((1 << 19, 64), (1 << 17, 256), (1 << 15, 1024), (1 << 14, 2048),
             (1 << 12, 8192))
    fn = _fused_fn(
        tuple(t for t, _ in grids), tuple(range(len(grids))), True, 8,
        2048, 512,
    )
    compiled = _compile_fused(one_chip, fn, grids, 8192)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 << 30
    assert _candidate_gathers_are_bucket_rows(compiled.as_text())


def _compile_fused(one_chip, fn, grids, rows):
    import jax.numpy as jnp

    d = 3
    grid_args = tuple(
        (
            _shape(one_chip, (t, cap), jnp.int32),
            (_shape(one_chip, (t, cap), jnp.float32),) * d,
            _shape(one_chip, (d,), jnp.float32),
            _shape(one_chip, (d,), jnp.float32),
            _shape(one_chip, (d,), jnp.int32),
        )
        for t, cap in grids
    )
    return fn.lower(
        _shape(one_chip, (N, d), jnp.float32),
        grid_args,
        _shape(one_chip, (rows, d), jnp.float32),
        _shape(one_chip, (rows,), jnp.int32),
        _shape(one_chip, (len(grids),), jnp.float32),
    ).compile()


def _candidate_gathers_are_bucket_rows(hlo: str, scope="trueknn.round"):
    """Every gather in ``scope`` that yields a block of candidates (the 27
    stencil cells by the query rows by the slots) fetches whole bucket
    rows (``slice_sizes={1,cap}``): no candidate's point or cell is
    gathered by id, element by element."""
    import re

    found = 0
    for line in hlo.splitlines():
        m = re.search(
            r"= \w+\[([\d,]+)\]\S* gather\(.*slice_sizes=\{([\d,]+)\}", line
        )
        if not m or scope not in line:
            continue
        shape = [int(x) for x in m.group(1).split(",")]
        sizes = [int(x) for x in m.group(2).split(",")]
        if len(shape) < 3:
            continue  # per-row gathers: queries, ids, the best-k lists
        found += 1
        if len(shape) != 3 or 27 not in shape[:2] or sizes != [1, shape[-1]]:
            return False
    return found > 0


def test_placed_fused_rounds_compile_for_four_chips(topo):
    """The placed fabric's whole shared-cut round loop on a 2x2 mesh:
    4 x 2^20 points in 8 shard slots, one 512-query batch.  Each device
    scores its two slots densely, (512, 2^19) distances per slot."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import PlacedFabric

    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    n, slots, d, qp = 4 * N, 8, 3, 512
    b = n // slots
    fab = PlacedFabric([np.zeros((b, d), np.float32)] * slots, mesh=mesh)

    def sd(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec))
        )

    compiled = fab._fused_rounds_fn("sq_l2", 8, False, 64, n).lower(
        sd((slots, b, d), jnp.float32, "shard", None, None),
        sd((slots,), jnp.int32, "shard"),
        sd((slots,), jnp.int32, "shard"),
        sd((slots, b + 1), jnp.int32, "shard", None),
        sd((qp, d), jnp.float32, None, None),
        sd((qp,), jnp.int32, None),
        sd((qp, slots), jnp.float32, None, None),
        sd((qp,), jnp.float32, None),
        sd((qp,), jnp.float32, None),
        sd((qp,), jnp.bool_, None),
        sd((1, 3), jnp.float32, None, None),
    ).compile()
    # per device: must fit a 16 GB v5e with room for the resident blocks
    assert compiled.memory_analysis().temp_size_in_bytes < 12 << 30
    assert "all-gather" in compiled.as_text()
