"""The main path's kernels, compiled for a DESCRIBED TPU v5e at the
widths the queue sends — no chip attached, nothing runs.

Interpret mode (every other Pallas test here) checks neither Mosaic's
block-shape rule nor the scoped-VMEM limit; the chip's own compiler,
which is installed in this sandbox, does.  A compile that passes is
not a chip run and says nothing about results or times — it guards
against kernels the chip would refuse (the tile ladder of PR 22).

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture, never while a module is
imported; the fixture is not autouse and lives here, so only the
xdist worker handed this file loads the TPU library; every compile
happens in the test's own process; all such tests stay in THIS file.
Shapes are steered here, not through an option of the program.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp

from ceph_tpu.ec import matrices
from ceph_tpu.ops import gf256_pallas, gf256_swar

LANES = gf256_pallas.LANES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip executable is written to the persistent cache
    # but cannot be read back without a chip (the next run would warn
    # and recompile): keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip):
    """The described chip's sharding, with the dtypes production has:
    tests/conftest.py turns jax_enable_x64 on, no product entry point
    does (and Mosaic refuses the i64 block indices x64 would trace)."""
    with jax.enable_x64(False):
        yield one_chip


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_planes(one_chip, matrix, T, donate=False):
    """Lower + compile the product's Pallas encode for (k, T, 128)
    planes with the tile gf_matmul_bytes would pick."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    tile, T_pad = gf256_swar.pallas_tile(T)
    fn = gf256_pallas._compiled(matrix.tobytes(), matrix.shape, tile,
                                False, False, donate)
    compiled = fn.jitted.lower(
        _spec(one_chip, (matrix.shape[1], T_pad, LANES), jnp.uint32),
        _spec(one_chip, (1,), jnp.uint32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's op is named for what it does, and its metadata
    # carries the family and the scope: stable names for a trace
    assert "%ec_encode" in text and "gf256_pallas/ec.encode" in text
    return compiled


def _k8m4():
    return matrices.isa_cauchy(8, 4)


def _k8_decode():
    from ceph_tpu.ec.codec import RSMatrixCodec

    rec, _ = RSMatrixCodec(8, 4, _k8m4()).recovery_matrix(
        [0, 2, 3, 5, 6, 7, 8, 11])
    return rec


# T = 256: a 1 MiB object's 128 KiB chunk; T = 2048: a full
# StripeBatchQueue batch (max_batch_cols, 1 Mi columns)
@pytest.mark.parametrize("T", [256, 2048])
def test_encode_k8m4_at_queue_widths(chip, T):
    _compile_planes(chip, _k8m4(), T)


@pytest.mark.parametrize("T", [256, 2048])
def test_square_decode_k8_donated_at_queue_widths(chip, T):
    # a square matrix compiled in, input aliased to the output
    _compile_planes(chip, _k8_decode(), T, donate=True)


# T = 1: a 4 KiB object's 512 B chunk, the narrowest Pallas row
@pytest.mark.parametrize("T", [1, 256, 2048])
def test_operand_decode_k8_at_queue_widths(chip, T):
    """What the queue's decode dispatches: the recovery matrix as an
    SMEM operand (one program per width for every survivor
    signature), the uploaded planes donated."""
    tile, T_pad = gf256_swar.pallas_tile(T)
    fn = gf256_pallas._compiled_operand(8, 8, tile, False, True)
    compiled = fn.jitted.lower(
        _spec(chip, (8 * 8 * 8,), jnp.uint32),
        _spec(chip, (8, T_pad, LANES), jnp.uint32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%ec_decode" in text and "gf256_pallas/ec.decode" in text


def test_clay_pair_matrix(chip):
    """clay's 1x2 pair transform over raveled node x layer subsets:
    widths like 27 * 512 (T = 27, no legal power-of-two tile)."""
    pair = np.array([[1, 2]], dtype=np.uint8)
    for T in (27, 64):
        _compile_planes(chip, pair, T)


@pytest.mark.parametrize("jobs", [1, 2, 4, 8])
def test_clay_k8m4d11_encode_program_at_the_cells_widths(chip, jobs):
    """The ONE device program of a clay k=8 m=4 d=11 encode batch of
    `jobs` coalesced 1 MiB objects (2,048 B a sub-chunk and job, the
    queue's array branch pads the jobs to a power of two): data words
    u32[8, 64, 512 * jobs] in, parity words u32[4, 64, 512 * jobs] out,
    uncoupling, the [4, 8] MDS code and re-coupling inside.  u32 alone
    crosses the program's boundary, and its op metadata names the
    family and the scope."""
    from ceph_tpu.ec import clay

    codec = clay.ClayCodec(8, 4, 11)
    W = 512 * jobs
    compiled = clay._encode_program(*codec._program_key).jitted.lower(
        _spec(chip, (8, 64, W), jnp.uint32)).compile()
    text = compiled.as_text()
    assert "gf256_clay/ec.encode" in text
    entry = [ln for ln in text.splitlines() if ln.startswith("ENTRY")]
    assert entry and "u8[" not in entry[0]
    assert f"-> u32[4,64,{W}]" in entry[0]
    # a batch's operands and temps are a few MiB of the chip's 16 GB
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("jobs", [1, 2, 4, 8])
def test_clay_k8m4d11_pair_and_solve_calls_at_the_cells_widths(chip, jobs):
    """What repair and the layered decode still call, at `jobs` coalesced
    1 MiB objects: the 1x2 pair transform over the raveled (node x
    layer) subsets they gather (a repair's 8 x 16 x 3/4 coupled symbols
    of the other columns, a decode level's 8 x 48), and the solve of q
    unknown nodes from the 8 known, the matrix an operand."""
    from ceph_tpu.ec.clay import ClayCodec

    codec = ClayCodec(8, 4, 11)
    s = 2048 * jobs
    for matrix, symbols in ((codec._uncouple_M, 8 * 12),
                            (codec._uncouple_M, 8 * 48),
                            (codec._repair_M, 3 * 16)):
        n = symbols * s
        assert n % 512 == 0   # what _engine sends to the Pallas kernel
        _compile_planes(chip, matrix, n // 512)
    tile, T_pad = gf256_swar.pallas_tile(16 * s // 512)
    fn = gf256_pallas._compiled_operand(4, 8, tile, False, False)
    compiled = fn.jitted.lower(
        _spec(chip, (4 * 8 * 8,), jnp.uint32),
        _spec(chip, (8, T_pad, LANES), jnp.uint32)).compile()
    assert "%ec_decode" in compiled.as_text()


@pytest.mark.parametrize("n", [1536, 3072, 6144, 13824, 513 * 512])
def test_tile_ladder_regression(chip, n):
    """n % 512 == 0 with T = n/512 lacking a divisor that is a
    multiple of 8: the old ladder picked tile 4 / 1 here and the
    lowering raised ValueError on a TPU.  513*512 takes the pad arm."""
    _compile_planes(chip, _k8m4(), n // 512)


def test_tile_1024_is_refused(chip):
    """The measured fact behind _PALLAS_MAX_TILE: the v5e's scoped
    VMEM does not hold a k=8 tile of 1024 sublane rows.  If a later
    libtpu accepts it, this fails and the cap can be re-derived."""
    m = np.ascontiguousarray(_k8m4(), dtype=np.uint8)
    fn = gf256_pallas._compiled(m.tobytes(), m.shape, 1024, False)
    with pytest.raises(Exception, match="(?i)vmem"):
        fn.jitted.lower(_spec(chip, (8, 2048, LANES), jnp.uint32),
                        _spec(chip, (1,), jnp.uint32)).compile()


def test_gf2_bitmatrix_kernel_k8m4(chip):
    """gf2_matmul_bytes_pallas at tile 2048: what shec and
    BitmatrixCodec._apply (ec/codec.py) dispatch on a TPU."""
    from ceph_tpu.ops import gf2_matmul

    bits = gf2_matmul.prepare_bitmatrix(_k8m4())
    compiled = gf2_matmul.gf2_matmul_bytes_pallas.jitted.lower(
        _spec(chip, bits.shape, jnp.int8),
        _spec(chip, (8, 131072), jnp.uint8), tile_n=2048).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_crc32c_rows_kernel_at_queue_shape(chip):
    """The fused crc pass for 8 jobs x 12 shards of 128 KiB chunks."""
    from ceph_tpu.ops import crc32c_device

    R, C = 96, 131072
    compiled = crc32c_device._rows_kernel(R, C).jitted.lower(
        _spec(chip, (R, C), jnp.uint8),
        _spec(chip, (R,), jnp.int32),
        _spec(chip, (R,), jnp.uint32)).compile()
    # the loop that is 97 % of the write cell's device time names its
    # family and scope in its op metadata (the `%while.N` of a trace)
    loops = [ln for ln in compiled.as_text().splitlines()
             if " while(" in ln and "op_name=" in ln]
    assert loops and all("crc32c_device/ec.crc32c" in ln for ln in loops)


def test_crush_sweep_chunk_program(chip):
    """sweep_device's stage-1 chunk program (one-shot fast pass, 2^19
    ids) for the 1024-OSD straw2 map (BASELINE config 6).  The whole
    three-stage sweep takes over a minute to compile and is left to
    the chip run (chip_smoke.py)."""
    from ceph_tpu.crush import map as cmap
    from ceph_tpu.crush import mapper

    m, root = cmap.build_flat_cluster(1024, hosts=64)
    steps = [(cmap.OP_TAKE, root, 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, 3, 1),
             (cmap.OP_EMIT, 0, 0)]
    fast = mapper.compile_rule(m.flatten(), steps, 3, None,
                               one_shot=True)
    compiled = jax.jit(fast).lower(
        _spec(chip, (1 << 19,), jnp.int32),
        _spec(chip, (1024,), jnp.uint32)).compile()
    # one program's temps fit the chip (16 GB HBM) with room to spare
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_crush_sweep_chunk_program_with_table_draws(chip):
    """The same chunk program on a map weighted by drive capacity (the
    benchmark's `crush-rep3-hetero-rack-1024osd`): every level holds
    unlike weights inside its buckets, so the one-shot pass draws one
    candidate a weight class through the draw tables (2 + 4 + 3 a
    replica, 54 table gathers an id; 192 when every item was drawn,
    PR 34).  It compiles and fits at chunk 2^19."""
    import json
    import os

    from ceph_tpu.crush import map as cmap
    from ceph_tpu.crush import mapper

    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "configs",
            "crush-rep3-hetero-rack-1024osd.json")) as f:
        cfg = json.load(f)
    m, ids = cmap.build_layered_cluster(
        1024, [(la["type_id"], la["size"]) for la in cfg["layers"]],
        cfg["osd_weights"])
    steps = [(cmap.OP_TAKE, ids[-1][0], 0),
             (cmap.OP_CHOOSELEAF_FIRSTN, 0, 1), (cmap.OP_EMIT, 0, 0)]
    fast = mapper.compile_rule(m.flatten(), steps, 3, None, one_shot=True)
    assert fast.draws == {"draw_fast": 0, "draw_class": 3, "draw_table": 0,
                          "draw_limb": 0}
    assert fast.full_draws == 3 * (2 + 4 + 3)
    compiled = jax.jit(fast).lower(
        _spec(chip, (1 << 19,), jnp.int32),
        _spec(chip, (1024,), jnp.uint32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30
