"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (``v5e:2x2``). Interpret mode and the CPU
backend cannot see what it refuses: an index map that traces to i64, a
kernel that does not fit VMEM, a collective that cannot be partitioned.
These cases compile — nothing runs, so they say nothing about results
or times.

This is the only file that describes a topology: the description loads
the TPU library, which one process may hold at a time, so it happens in
a module-scoped fixture (never at import) and every compile is made in
this process.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from thrill_tpu.core import pallas_kernels as pk
from thrill_tpu.core import pallas_sort as ps
from thrill_tpu.parallel import mesh as mesh_mod
from thrill_tpu.parallel.mesh import MeshExec


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
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# rows at the edge of every kernel's own gate (rows_ok: n < MAX_ROWS)
_N_EDGE = pk.MAX_ROWS - 1

_KERNELS = {
    "partition_histogram": (
        lambda d: pk.partition_histogram_pallas(d, 256),
        [((_N_EDGE,), jnp.int32)]),
    "segment_sum": (
        lambda s, v: pk.segment_sum_pallas(s, v, pk.SEGSUM_MAX_SEGS),
        [((_N_EDGE,), jnp.int32), ((_N_EDGE,), jnp.float32)]),
    "presence_fill": (
        lambda h, v: pk.presence_fill_pallas(h, v, pk.PRESFILL_MAX_REGS),
        [((_N_EDGE,), jnp.int32), ((_N_EDGE,), jnp.bool_)]),
    "stable_partition_offsets": (
        lambda d: ps.stable_partition_offsets_pallas(d, 256),
        [((_N_EDGE,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_pallas_kernel_compiles_at_its_gate_edge(one_chip, name):
    """Every Pallas kernel, x64 on as the package sets it, at the
    largest size its own ``*_ok`` gate admits."""
    assert jax.config.jax_enable_x64
    assert pk.segment_sum_ok(pk.SEGSUM_MAX_SEGS, _N_EDGE)
    assert pk.presence_fill_ok(pk.PRESFILL_MAX_REGS, _N_EDGE)
    fn, specs = _KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_presence_fill_past_its_gate_is_refused_for_vmem(one_chip):
    """Why PRESFILL_MAX_REGS is what it is: twice the gate does not fit
    the chip's fast memory, and the refusal is not an HBM OOM for the
    memory-pressure ladder to spill and retry."""
    from thrill_tpu.mem.pressure import is_oom_error
    regs = 2 * pk.PRESFILL_MAX_REGS
    args = [jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((1 << 20,), jnp.bool_, sharding=one_chip)]
    with pytest.raises(Exception) as ei:
        jax.jit(lambda h, v: pk.presence_fill_pallas(h, v, regs)) \
            .lower(*args).compile()
    assert "vmem" in str(ei.value)
    assert not is_oom_error(ei.value)


def _terasort_shapes(mex, n):
    sds = jax.ShapeDtypeStruct
    return [sds((mex.num_workers, 1), jnp.int32, sharding=mex.sharded),
            sds((mex.num_workers, n, 10), jnp.uint8, sharding=mex.sharded),
            sds((mex.num_workers, n, 90), jnp.uint8, sharding=mex.sharded)]


@pytest.fixture
def tpu_layouts(monkeypatch):
    """What a TPU backend picks by itself and this CPU process would
    not: u32 key words and packed row movement (the code asks
    ``jax.default_backend()``, which still says cpu here)."""
    monkeypatch.setenv("THRILL_TPU_SORT_U32", "1")
    monkeypatch.setenv("THRILL_TPU_PACK_MOVE", "1")


# The W=1 TeraSort program (encode_key_words -> u32 split -> argsort ->
# packed gathers of the 10- and 90-byte columns), with the validity
# word the fused path carries. At 2^20 rows neither engine that auto
# picks compiles in seconds on this toolchain (xla 335 s; chunked 96 s,
# 235 s before its merge stages were rolled into one loop; CHANGES.md
# PR 22), so the kept cases are the xla program at a size that does and
# the bitonic engine — the same rolled loop without the tile sort.
@pytest.mark.parametrize("engine,n", [("xla", 1 << 12),
                                      ("bitonic", 1 << 20)])
def test_w1_terasort_program_compiles(topo, tpu_layouts, monkeypatch,
                                      engine, n):
    from thrill_tpu.api.ops.sort import _w1_sort_fn
    monkeypatch.setenv("THRILL_TPU_SORT_IMPL", engine)
    mex = MeshExec(devices=topo.devices[:1])
    treedef = jax.tree.structure({"key": 0, "value": 0})
    prog = mex.smap(_w1_sort_fn(lambda r: r["key"], treedef, False), 3)
    compiled = prog.lower(*_terasort_shapes(mex, n)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= n * 100


def test_chunked_merge_tree_compiles(one_chip, tpu_layouts):
    """The chunked engine's tile sort + bitonic merge tree (what auto
    picks on the chip above 64K rows), cut to 16 tiles of 1024 rows so
    that it compiles in seconds."""
    from thrill_tpu.core.device_sort import _chunked_argsort
    n = 1 << 14
    words = [jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)] * 4
    jax.jit(lambda *w: _chunked_argsort(
        list(w), chunk=1 << 10, index_dtype=jnp.uint32)) \
        .lower(*words).compile()


class _Compiled(Exception):
    """Carries the executable out of the production code path."""

    def __init__(self, compiled):
        super().__init__("compiled instead of dispatched")
        self.compiled = compiled


def test_w4_exchange_chunk_program_compiles(topo, tpu_layouts, monkeypatch):
    """The dense phase-B exchange program exactly as
    ``_dispatch_chunked`` builds it, on a 4-chip mesh: the dispatch
    choke point is turned into "compile for the described chips" — no
    array exists, shapes go in."""
    from thrill_tpu.data import exchange

    def compile_instead(self, args, kwargs):
        raise _Compiled(self._jitted.lower(*args, **kwargs).compile())

    monkeypatch.setattr(mesh_mod._CountedJit, "_dispatch", compile_instead)
    monkeypatch.setenv("THRILL_TPU_XCHG_CHUNKS", "1")
    mex = MeshExec(devices=topo.devices)
    W, cap = mex.num_workers, 1 << 18
    assert W == 4
    sds = jax.ShapeDtypeStruct
    leaves = [sds((W, cap, 10), jnp.uint8, sharding=mex.sharded),
              sds((W, cap, 90), jnp.uint8, sharding=mex.sharded)]
    dest = sds((W, cap), jnp.int32, sharding=mex.sharded)
    smat = sds((W, W), jnp.int32,
               sharding=NamedSharding(mex.mesh, P()))
    with pytest.raises(_Compiled) as ei:
        exchange._dispatch_chunked(
            mex, jax.tree.structure({"key": 0, "value": 0}), dest,
            leaves, smat, M_pad=cap // 2, out_cap=2 * cap)
    text = ei.value.compiled.as_text()
    assert "all-to-all" in text
    mem = ei.value.compiled.memory_analysis()
    # per device: the received rows fit many times over in 16 GB
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 8 << 30


def test_leaf_range_analysis_compiles_on_four_chips(topo):
    """The exchange narrowing's range analysis all-reduces int64 minima
    and maxima. The TPU compiler lowers a 64-bit all-reduce only for
    sums (an s64 pmin: "Supported lowering only of Sum all reduce"), so
    it gathers the per-worker scalars and reduces them locally."""
    from thrill_tpu.data import exchange
    mex = MeshExec(devices=topo.devices)

    def f(x, counts):
        valid = jnp.arange(x.shape[1]) < counts[0, 0]
        return exchange.leaf_ranges_traced([x[0]], valid)

    sds = jax.ShapeDtypeStruct
    mex.smap(f, 2, out_specs=P()).lower(
        sds((4, 1024), jnp.int64, sharding=mex.sharded),
        sds((4, 1), jnp.int32, sharding=mex.sharded)).compile()


def test_dense_fold_writes_no_row_by_item_array(one_chip):
    """ReduceToIndex's dense fold at k-means' size (2^22 items into 16
    rows: a binary64 ``[n, 3]`` and ``[n]`` sum, an int64 "first"): each
    leaf is read by one reduce fusion with the compare inside it, so no
    array of one row per item and output row is written, and the
    temporaries stay under what one such array would take."""
    from thrill_tpu.api.ops import reduce as reduce_mod
    n, rows = 1 << 22, 16
    sds = jax.ShapeDtypeStruct
    args = [sds((n,), jnp.int32, sharding=one_chip),
            sds((n,), jnp.int64, sharding=one_chip),
            sds((n, 3), jnp.float64, sharding=one_chip),
            sds((n,), jnp.float64, sharding=one_chip)]
    compiled = jax.jit(lambda p, i, x, c: reduce_mod._scatter_reduce_apply(
        (i, x, c), p, rows, ("first", "sum", "sum"), None)).lower(
        *args).compile()
    text = compiled.as_text()
    assert f"[{n},{rows}]" not in text and f"[{rows},{n}]" not in text
    assert not re.search(r"\b(sort|scatter)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < n * rows * 4


def test_send_counts_compiles_to_one_reduce_on_four_chips(topo):
    """``send_counts`` at W = 4 over 2^22 rows a chip, as phase A and
    ``Sort``'s classification run it: the compare fuses into its reduce,
    so no scatter runs, no ``[n, W]`` array is written and the program
    needs no temporaries of a column's size."""
    from thrill_tpu.data import exchange
    mex = MeshExec(devices=topo.devices)
    W, n = mex.num_workers, 1 << 22
    compiled = mex.smap(lambda d: exchange.send_counts(d[0], W), 1,
                        out_specs=P()).lower(jax.ShapeDtypeStruct(
                            (W, n), jnp.int32,
                            sharding=mex.sharded)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bscatter\(", text)
    # the [W, n] compare lives inside the fusion; the program's own
    # instructions hold no such array
    entry = text[text.index("ENTRY"):]
    assert not re.search(rf"\[({W}|{W + 1}),{n}\]|\[{n},({W}|{W + 1})\]",
                         entry)
    assert compiled.memory_analysis().temp_size_in_bytes < n
