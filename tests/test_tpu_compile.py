"""Compile the main path's kernels and the csr device FD program for a
described TPU v5e, at the widths of ``chip_smoke.py``'s phase-(b) graph
(``tests/goldens/record_chip_smoke.py``'s ``peel`` graph: |E| =
200,000, 16,483,275 V-centred wedges in 15,423,111 pairs).

Nothing runs: the TPU compiler is installed on CPU-only machines and
compiles for a chip that is described, not attached, so Mosaic's tiling
and VMEM rules and the device's HBM capacity are checked here at no
chip time.  Interpret-mode parity lives in ``test_kernels.py`` and
``test_csr.py``; this file proves the same kernels lower for the chip.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import csr
from repro.core.peel import (
    _FD_COMPACT_FLOOR, _fd_tip_device, _fd_wing_chunk, _fd_wing_device)
from repro.kernels import ops as kops

# phase-(b) widths: W0 max 169 wedges per pair → 256 slot lanes (wing);
# a U vertex sits in at most 20,514 directed pairs → 20,608 lanes (tip);
# the tiled ⋈init packs 512-wide slot rows; the dense adjacency is
# 40,000 × 10,000.  Row counts are the largest one launch sees, or a
# 2^16-row stripe where the whole-graph slot layout would crowd HBM
# (wing: 15.4M rows × 256 lanes).
M, N_U, N_V = 200_000, 40_000, 10_000
N_PAIRS = 15_423_111
WEDGE_BUCKET = 16_777_216      # peelspec._bucket_pad(16_483_275)
WING_LANES, TIP_LANES, TILE_LANES = 256, 20_608, 512
STRIPE = 1 << 16
HBM_BYTES = 15.75 * 2 ** 30    # v5e HBM as its compiler reports it


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(lowered):
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used <= HBM_BYTES, used
    return compiled.as_text()


KERNELS = {
    # wing CD support update through the blocked kernel (--use-pallas)
    "support_update": lambda s: kops.support_update.lower(
        _s(s, (STRIPE, WING_LANES), bool), _s(s, (STRIPE, WING_LANES), bool),
        _s(s, (STRIPE, WING_LANES), bool), _s(s, (STRIPE,), jnp.int32),
        interpret=False),
    # per-pair wedge counts (wedge_count kernel, wing slot layout)
    "wedge_count": lambda s: kops.pair_wedge_counts.lower(
        _s(s, (STRIPE, WING_LANES), bool), interpret=False),
    # tip CD delta (wedge_count kernel over vertex-major pair slots)
    "wedge_count_tip": lambda s: kops.tip_slot_loss.lower(
        _s(s, (N_U, TIP_LANES), jnp.int32), interpret=False),
    # tiled ⋈init: one 2^20-wedge tile as 512-wide int32 slot rows
    "wedge_count_tile": lambda s: kops._tile_row_counts_inner.lower(
        _s(s, (1 << 20, TILE_LANES), jnp.int32), 8, 128, False),
    # dense per-vertex butterflies over the whole adjacency
    "vertex_count": lambda s: kops.vertex_butterflies.lower(
        _s(s, (N_U, N_V), jnp.float32), interpret=False),
    # one 1024-row strip against the padded adjacency
    "vertex_count_tile": lambda s: kops._vertex_tile_inner.lower(
        _s(s, (1024, 10_112), jnp.float32),
        _s(s, (40_064, 10_112), jnp.float32), 128, 128, False),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    hlo = _compile(KERNELS[name](one_chip))
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"


def test_wing_fd_while_compiles_for_v5e(one_chip):
    """The device FD program of the largest bucket a phase-(b)
    wing partition can take (the whole wedge list)."""
    s = one_chip
    hlo = _compile(_fd_wing_device.lower(
        _s(s, (M,), bool), _s(s, (M,), jnp.int32),
        _s(s, (WEDGE_BUCKET,), bool), _s(s, (N_PAIRS,), jnp.int32),
        _s(s, (WEDGE_BUCKET,), jnp.int32), _s(s, (WEDGE_BUCKET,), jnp.int32),
        _s(s, (WEDGE_BUCKET,), jnp.int32), n_pairs=N_PAIRS, m=M))
    assert "while" in hlo


@pytest.mark.parametrize("size", [WEDGE_BUCKET, _FD_COMPACT_FLOOR])
def test_wing_fd_chunk_compiles_for_v5e(one_chip, size):
    """The compacting wing FD's loop at the largest bucket and at the
    ladder's floor, where it runs until the partition drains."""
    s = one_chip
    i32 = jnp.int32
    state = (_s(s, (M,), bool), _s(s, (M,), i32),
             (_s(s, (size,), bool), _s(s, (N_PAIRS,), i32)),
             _s(s, (M,), i32), _s(s, (), i32), _s(s, (), i32),
             _s(s, (), i32))
    hlo = _compile(_fd_wing_chunk.lower(
        state, _s(s, (size,), i32), _s(s, (size,), i32),
        _s(s, (size,), i32), n_pairs=N_PAIRS, m=M))
    assert "while" in hlo


def test_tip_fd_while_compiles_for_v5e(one_chip):
    """The same for tip (side u): the pair list of the whole graph."""
    s = one_chip
    hlo = _compile(_fd_tip_device.lower(
        _s(s, (N_U,), bool), _s(s, (N_U,), jnp.int32),
        _s(s, (WEDGE_BUCKET,), jnp.int32), _s(s, (WEDGE_BUCKET,), jnp.int32),
        _s(s, (WEDGE_BUCKET,), jnp.int32), n=N_U))
    assert "while" in hlo


# graph500_hub.tip_v at scale_u 18: the 512-vertex side over 2^18 items,
# 2,079,918 edges (bucket 2,097,152), 130,601 pairs (bucket 131,072)
HUB_ROWS, HUB_K, HUB_EDGES, HUB_PAIRS = 512, 1 << 18, 1 << 21, 1 << 17


def test_tip_pair_counts_compiles_for_v5e(one_chip):
    """The pair-count programs of the hub cell: the edges set in an int8
    adjacency, then its product with itself on the MXU, int32 out, as
    one dot and nothing else."""
    s = one_chip
    adj = _compile(csr.tip_pair_adjacency.lower(
        _s(s, (HUB_EDGES,), jnp.int32), rows=HUB_ROWS, k=HUB_K))
    # every operation of the adjacency is named after it, no index sort
    entry = adj[adj.index("ENTRY"):]
    assert " sort(" not in entry
    for line in entry.splitlines():
        if "fusion(" in line:
            assert 'op_name="jit(tip_pair_adjacency)/' in line, line
    hlo = _compile(csr.tip_pair_counts.lower(
        _s(s, (HUB_ROWS, HUB_K), jnp.int8)))
    ops = [line for line in hlo[hlo.index("ENTRY"):].splitlines()
           if " = " in line and "parameter(" not in line]
    # a device trace times the product alone by this one name
    assert len(ops) == 1 and "s32[512,512]" in ops[0], ops
    assert 'op_name="jit(tip_pair_counts)/dot_general"' in ops[0]


def test_wide_tip_fd_while_compiles_for_v5e(one_chip):
    """The hub cell's device FD program with 64-bit supports and pair
    counts, traced in the scoped 64-bit mode the engine uses."""
    s = one_chip
    with csr.count_scope(64):
        hlo = _compile(_fd_tip_device.lower(
            _s(s, (HUB_ROWS,), bool), _s(s, (HUB_ROWS,), jnp.int64),
            _s(s, (HUB_PAIRS,), jnp.int32), _s(s, (HUB_PAIRS,), jnp.int32),
            _s(s, (HUB_PAIRS,), jnp.int64), n=HUB_ROWS))
    assert "while" in hlo and "s64[512]" in hlo
