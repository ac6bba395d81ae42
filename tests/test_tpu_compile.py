"""The chip's compiler, asked from the CPU sandbox: AOT-compile the Pallas
kernels and the fused serving reads for a DESCRIBED TPU v5e (no chip is
attached; nothing runs) at the widths `console serve` really uses.

Interpret mode — the only way the other tests run these kernels — cannot
see what Mosaic refuses: i64 index maps under the package's x64 setting,
a block that overflows scoped VMEM, an unaligned slice.  These compiles
can, at no chip time.  A pass here is a compile, never a chip run.

The topology is described inside a fixture (only the xdist worker that is
handed this file loads the TPU library), everything built from it is built
in fixtures or tests, and the persistent compile cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type
from antidote_tpu.materializer import pallas_kernels as pk
from antidote_tpu.store import TypedTable

#: kernel batch and the `console serve` default widths (set_slots,
#: ops_per_key); D = max_dcs is 8 in `console serve`; 4 is the narrow case
ROWS, E, K = 16_384, 16, 16
DCS = (4, 8)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def shape(one_chip, no_persistent_cache):
    assert jax.config.jax_enable_x64, "the in-trace callers trace with x64 on"

    def mk(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return mk


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("d", DCS)
def test_counter_fold_compiles(shape, d):
    _compile(
        lambda *a: pk.counter_fold_deltas(*a, interpret=False),
        shape((ROWS, K)), shape((ROWS, K, d)), shape((ROWS,)),
        shape((ROWS, d)), shape((ROWS, d)),
    )


@pytest.mark.parametrize("d", DCS)
def test_orset_presence_compiles(shape, d):
    _compile(
        lambda *a: pk.orset_presence(*a, interpret=False),
        shape((ROWS, E, d)), shape((ROWS, E, d)), shape((ROWS, E)),
    )


@pytest.mark.parametrize("d", DCS)
def test_set_aw_fold_compiles(shape, d):
    """At the block the server uses: the default, chosen from the shapes
    (256, the old literal, needs 18.9 MB / 28.0 MB of the 16 MB scoped
    VMEM at D=4 / D=8)."""
    assert pk.set_aw_fold_block(E, K, d) == 64
    state = {
        "elems": shape((ROWS, E), jnp.int64), "addvc": shape((ROWS, E, d)),
        "rmvc": shape((ROWS, E, d)), "ovf": shape((ROWS,)),
    }
    _compile(
        lambda *a: pk.set_aw_fold(*a, interpret=False),
        state, shape((ROWS, K, 1), jnp.int64), shape((ROWS, K, 1 + d)),
        shape((ROWS, K, d)), shape((ROWS, K)), shape((ROWS,)),
        shape((ROWS, d)), shape((ROWS, d)),
    )


def test_set_aw_fold_block_fails_loudly():
    """A configuration no block fits is an error, not a reason to leave
    the kernel for another fold."""
    with pytest.raises(ValueError, match="scoped VMEM"):
        pk.set_aw_fold_block(E, K, 512)


@pytest.mark.parametrize("tyname,strategy", [
    ("set_aw", "pallas_set_aw"), ("counter_pn", "pallas_counter"),
])
def test_fused_serving_read_compiles(shape, monkeypatch, tyname, strategy):
    """The jitted one-launch serving read of a `use_pallas` table (head
    gather + version select + ring fold kernel + resolve, with the
    presence kernel for set_aw) at `console serve`'s default widths and
    its largest batch bucket, full ring (kmax=0)."""
    # the code under test asks jax for the backend and would take its
    # off-TPU branches here: steer it to the branch a TPU takes
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    cfg = AntidoteConfig(n_shards=16, max_dcs=8, keys_per_table=1024,
                         use_pallas=True)
    table = TypedTable(get_type(tyname), cfg)
    assert table._fold_strategy() == strategy
    m = cfg.batch_buckets[-1]
    like = lambda x: shape(x.shape, x.dtype)
    text = _compile(
        table._read_resolved_flat_fn(strategy, 0),
        *jax.tree.map(like, (
            table.head, table.head_vc, table.snap, table.snap_vc,
            table.snap_seq, table.ops_a, table.ops_b, table.ops_vc,
            table.ops_origin,
        )),
        shape((m, 3 + cfg.max_dcs)),   # the staged operand
    )
    # set_aw: the fold kernel and the presence kernel
    assert text.count("tpu_custom_call") >= (2 if tyname == "set_aw" else 1)


@pytest.mark.parametrize("tyname,strategy", [
    ("set_aw", "pallas_set_aw"), ("counter_pn", "pallas_counter"),
])
def test_mesh_routed_read_compiles(topo, no_persistent_cache, monkeypatch,
                                   tyname, strategy):
    """The routed [P, M'] reads of a table placed over the four chips of
    the described host (`console serve --mesh-devices 4 --pallas`): Mosaic
    kernels cannot be partitioned automatically, so they must sit under
    the table's explicit shard_map — and the program needs no collective
    (each device reads its own shards)."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    placed = NamedSharding(Mesh(np.array(topo.devices), ("shard",)),
                           PartitionSpec("shard"))
    cfg = AntidoteConfig(n_shards=16, max_dcs=8, keys_per_table=1024,
                         use_pallas=True)
    table = TypedTable(get_type(tyname), cfg)
    table.set_sharding(placed)  # placement only: nothing is put anywhere
    p, m = cfg.n_shards, cfg.batch_buckets[1]

    def mk(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=placed)

    like = lambda x: mk(x.shape, x.dtype)
    head = jax.tree.map(like, (table.head, table.head_vc))
    fold = _compile(
        table._read_resolved_fn(strategy, 0), *head,
        *jax.tree.map(like, (
            table.snap, table.snap_vc, table.snap_seq, table.ops_a,
            table.ops_b, table.ops_vc, table.ops_origin,
        )),
        mk((p, m), jnp.int64), mk((p, m)), mk((p, m, cfg.max_dcs)),
    )
    assert "all-gather" not in fold and "all-reduce" not in fold
    latest = table._latest_resolved_fn.lower(
        *head, mk((p, m), jnp.int64), mk((p, m, cfg.max_dcs))
    ).compile().as_text()
    assert ("tpu_custom_call" in latest) == (tyname == "set_aw")


@pytest.mark.parametrize("bucket", [0, 1])
def test_mesh_gather_compiles_at_2m_rows(topo, no_persistent_cache,
                                         monkeypatch, bucket):
    """`jit_antidote_mesh_gather`, the routed epoch read of the deployment
    `set_aw_2m_mesh4` (`--mesh-devices 4 --pallas --shards 16
    --keys-per-table 131072`), at its real shapes on the described host:
    frozen head buffers [16, 131072, ...] a quarter on each chip, and the
    routed [16, M'] buckets its window meets (M' = 64: up to 64 one-object
    reads merge; M' = 512: the warm walk's 1,024-object read).  The
    program needs no collective, and what it reserves fits a 16 GB chip
    beside that chip's 4.42 GB share of the tables."""
    from antidote_tpu.parallel.mesh import MeshServingPlane

    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices), ("shard",))
    placed = NamedSharding(mesh, PartitionSpec("shard"))
    rows = 131_072
    cfg = AntidoteConfig(n_shards=16, max_dcs=8, keys_per_table=rows,
                         use_pallas=True)
    # a few rows are allocated here; the shapes compiled are the real ones
    table = TypedTable(get_type("set_aw"), cfg, n_rows=8)
    # the plane builds its mesh from jax.devices(), the CPU's here: hand
    # it the described one instead
    plane = MeshServingPlane.__new__(MeshServingPlane)
    plane.mesh = mesh
    p, m = cfg.n_shards, cfg.batch_buckets[bucket]

    def mk(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=placed)

    full = lambda x: mk((p, rows) + x.shape[2:], x.dtype)
    compiled = plane._build_gather(table).lower(
        *jax.tree.map(full, (table.head, table.head_vc)),
        mk((p, m), jnp.int64), mk((p, m, cfg.max_dcs)),
    ).compile()
    text = compiled.as_text()
    assert "jit_antidote_mesh_gather" in text
    assert "tpu_custom_call" in text, "set_aw resolves in its Mosaic kernel"
    assert "all-gather" not in text and "all-reduce" not in text
    mem = compiled.memory_analysis()
    head_share = sum(
        int(np.prod((p // 4, rows) + x.shape[2:])) * x.dtype.itemsize
        for x in jax.tree.leaves((table.head, table.head_vc)))
    assert mem.argument_size_in_bytes >= head_share      # per device
    reserved = (mem.temp_size_in_bytes + mem.output_size_in_bytes
                + mem.generated_code_size_in_bytes)
    table_share = 4.42e9       # ISSUE 26: 17.7 GB of tables over 4 chips
    assert table_share + reserved < 16e9, (
        f"the gather reserves {reserved / 1e9:.2f} GB on each device")
    print(f"mesh_gather [16, {m}] at 16 x {rows} rows: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, output "
          f"{mem.output_size_in_bytes / 1e6:.3f} MB a device")


@pytest.mark.parametrize("mb", [64, 512])
@pytest.mark.parametrize("window", [1, 0])
@pytest.mark.parametrize("placement", ["one_chip", "mesh4"])
def test_commit_scatter_compiles_in_place_at_set_aw_shapes(
        topo, no_persistent_cache, placement, window, mb):
    """`jit_antidote_commit_scatter_w<n>`, the one program of a commit
    group, at the shapes of the two `set_aw` deployments: [16, 65536] on
    one chip (`set_aw_1m`, `set_aw_1m_rw`) and [16, 131072] over the
    host's four (`set_aw_2m_mesh4`: [4, 131072] a device), at both batch
    buckets a commit group of requests fills, 64 and 512 (a larger one
    is a fill's and is scattered: `typed_table._ROW_WRITE_MAX_BATCH`).
    All six donated tables alias their outputs, a mesh program
    needs no collective, and fusing the ring writes with the head update
    costs no memory: the program's temporaries stay within 64 MB of what
    the head update reserves as a program of its own on one device's
    block.  Since PR 33 both write their rows in the tables' own layout
    (`_write_rows`) and that is 0.2 GB on one chip, 0.03 GB a mesh
    device — the int64 fields' split — where a scatter re-laid whole
    head fields out: 4.3 GB and 2.15 GB — at either bucket under
    ROW_PROGRAM_TEMP_LIMIT.  This is the test that keeps
    ring writes and head update ONE program: if it ever fails, they go
    back to two programs fed the same staged operand."""
    from antidote_tpu.store import typed_table

    mesh = Mesh(np.array(topo.devices), ("shard",))
    placed = NamedSharding(mesh, PartitionSpec("shard"))
    one = SingleDeviceSharding(topo.devices[0])
    if placement == "one_chip":
        rows, devices, table_sh, staged_sh = 65_536, 1, one, one
    else:
        rows, devices = 131_072, 4
        table_sh, staged_sh = placed, NamedSharding(mesh, PartitionSpec())
    cfg = AntidoteConfig(n_shards=16, max_dcs=8, keys_per_table=rows,
                         use_pallas=True)
    # a few rows are allocated here; the shapes compiled are the real ones
    table = TypedTable(get_type("set_aw"), cfg, n_rows=8)
    if placement == "mesh4":
        table.set_sharding(placed)  # placement only: nothing is put anywhere
    p = cfg.n_shards

    def tables(shards, sharding):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (shards, rows) + x.shape[2:], x.dtype, sharding=sharding),
            ((table.ops_a, table.ops_b, table.ops_vc, table.ops_origin),
             (table.head, table.head_vc)))

    rings, head = tables(p, table_sh)
    compiled = table._commit_scatter_for(window).lower(
        *rings, *head, jax.ShapeDtypeStruct(
            (mb, table._staged_cols), jnp.int32, sharding=staged_sh),
    ).compile()
    text = compiled.as_text()
    assert f"jit_antidote_commit_scatter_w{window}" in text
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    mem = compiled.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves((rings, head))) // devices
    assert mem.alias_size_in_bytes >= held, "a donated table is copied"
    block_rings, block_head = tables(p // devices, one)
    keys = [jax.ShapeDtypeStruct((mb,), jnp.int32, sharding=one)] * 4
    head_alone = jax.jit(
        typed_table._head_update_body(table.ty, cfg, window),
        donate_argnums=(0, 1),
    ).lower(*block_head, *block_rings, *keys).compile().memory_analysis()
    assert (mem.temp_size_in_bytes
            <= head_alone.temp_size_in_bytes + 64 * 2**20), (
        f"fused {mem.temp_size_in_bytes / 1e9:.3f} GB of temporaries, the "
        f"head update alone {head_alone.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < ROW_PROGRAM_TEMP_LIMIT
    reserved = mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
    table_share = 8.87e9 if devices == 1 else 4.45e9   # PERF §4, in use
    assert table_share + reserved < 16e9
    print(f"commit_scatter_w{window} {placement} bucket {mb}: tables "
          f"{held / 1e9:.3f} GB aliased, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB (head update alone "
          f"{head_alone.temp_size_in_bytes / 1e9:.3f} GB) a device")


# ---------------------------------------------------------------------------
# ISSUE 33: programs whose work is a bucket of rows reserve by the rows
# ---------------------------------------------------------------------------
#: what any one of them may reserve beside the tables at a deployment's
#: shapes, whatever the rows: the row writes themselves need a few tiles;
#: what is left is the compiler's 64-bit rewrite, which splits an int64
#: field into halves and joins them again (0.27 GB for a GC's snap.elems
#: at [16, 65536], 0.07 GB a mesh device).  The parent's GC reserved
#: 8.00 GB here.
ROW_PROGRAM_TEMP_LIMIT = 512 * 2**20


def _parent_gc(snap, snap_vc, snap_seq, head, head_vc, rows, new_seqs):
    """`_gc_fn` as it stood before ISSUE 33 (vmapped `.at[rows,
    slot].set`), kept here as the case the limit has to refuse."""
    from antidote_tpu.clock import orddict

    def per_shard(snap, snap_vc, snap_seq, head, head_vc, rows, seqs):
        slot = orddict.insert_slot(snap_seq[rows])
        snap2 = {f: x.at[rows, slot].set(head[f][rows], mode="drop")
                 for f, x in snap.items()}
        return (snap2,
                snap_vc.at[rows, slot].set(head_vc[rows], mode="drop"),
                snap_seq.at[rows, slot].set(seqs, mode="drop"))

    return jax.vmap(per_shard)(snap, snap_vc, snap_seq, head, head_vc,
                               rows, new_seqs)


@pytest.fixture(scope="module")
def set_aw_programs(topo):
    """The row programs of a `set_aw` base table and of its tier tables,
    lowered at a placement's real shapes: `compile(what)` -> (compiled,
    bytes of the donated tables on one device)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from antidote_tpu.store.kv import scaled_cfg, tier_rows

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    mesh = Mesh(np.array(topo.devices), ("shard",))
    placed = NamedSharding(mesh, PartitionSpec("shard"))
    one = SingleDeviceSharding(topo.devices[0])
    mb = 64

    def build(placement):
        if placement == "one_chip":
            rows, devices, tsh, bsh = 65_536, 1, one, one
        else:
            rows, devices, tsh = 131_072, 4, placed
            bsh = NamedSharding(mesh, PartitionSpec())
        base = AntidoteConfig(n_shards=16, max_dcs=8, keys_per_table=rows,
                              use_pallas=True)

        def table(tier):
            t = TypedTable(get_type("set_aw"), scaled_cfg(base, tier),
                           n_rows=8)
            if devices > 1:
                t.set_sharding(placed)   # placement only
            n = rows if tier == 0 else tier_rows(base, tier)
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    (16, n) + x.shape[2:], x.dtype, sharding=tsh),
                t._tree())
            return t, shapes

        def b(*dims, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=bsh)

        def held(*trees):
            return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                       for x in jax.tree.leaves(trees)) // devices

        def compile_(what):
            t0, s0 = table(0)
            if what in ("gc", "parent_gc"):
                donated = (s0["snap"], s0["snap_vc"], s0["snap_seq"])
                if what == "gc":
                    fn, batch = t0._gc_fn, (b(mb), b(mb),
                                            b(mb, dtype=jnp.int64), b())
                else:
                    fn = jax.jit(_parent_gc, donate_argnums=(0, 1, 2))
                    batch = (jax.ShapeDtypeStruct((16, mb), jnp.int64,
                                                  sharding=tsh),) * 2
                return fn.lower(*donated, s0["head"], s0["head_vc"],
                                *batch).compile(), held(donated)
            if what == "clear_rows":      # a promotion's source row
                return t0._clear_rows_fn.lower(
                    s0, b(mb), b(mb), b()).compile(), held(s0)
            if what == "read":
                args = (s0["head"], s0["head_vc"], s0["snap"], s0["snap_vc"],
                        s0["snap_seq"], s0["ops_a"], s0["ops_b"],
                        s0["ops_vc"], s0["ops_origin"])
                if devices == 1:
                    fn = t0._read_resolved_flat_fn("pallas_set_aw", 0)
                    batch = (b(mb, 3 + 8),)   # the staged operand
                else:
                    fn = t0._read_resolved_fn("pallas_set_aw", 0)
                    mk = lambda *d, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
                        d, dtype, sharding=tsh)
                    batch = (mk(16, mb, dtype=jnp.int64), mk(16, mb),
                             mk(16, mb, 8))
                return fn.lower(*args, *batch).compile(), 0
            src, dst = {"promote_0_1": (0, 1), "promote_2_3": (2, 3),
                        "grow_1": (1, 1)}[what]
            td, sd = table(dst)
            if what == "grow_1":
                return td._grow_fn.lower(sd).compile(), 0
            state = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype,
                                               sharding=bsh),
                table(src)[0]._tree())
            return td._install_row_fn.lower(
                sd, state, b(1), b(1), b(dtype=jnp.int64), b()
            ).compile(), held(sd)

        return compile_

    yield {p: build(p) for p in ("one_chip", "mesh4")}
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("what", ["gc", "promote_0_1", "promote_2_3",
                                  "clear_rows", "read", "grow_1"])
@pytest.mark.parametrize("placement", ["one_chip", "mesh4"])
def test_row_programs_reserve_by_the_rows(set_aw_programs, monkeypatch,
                                          placement, what):
    """GC, the two halves of a tier promotion (`tier_promote` writes the
    destination tier's row, tier 0 -> 1 and 2 -> 3; `clear_rows` clears
    the source's), the versioned read and a tier table's grow, at the
    shapes of `set_aw_1m_rw` ([16, 65536] on one chip) and of the mesh
    deployment ([4, 131072] a device): each reserves under
    ROW_PROGRAM_TEMP_LIMIT beside the tables, a number that does not
    hang on the table's rows; every donated table aliases its output;
    a mesh program needs no collective."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    compiled, held = set_aw_programs[placement](what)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert mem.temp_size_in_bytes < ROW_PROGRAM_TEMP_LIMIT, (
        f"{what} reserves {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.alias_size_in_bytes >= held, "a donated table is copied"
    if what != "grow_1":
        for collective in ("all-gather", "all-reduce", "all-to-all",
                           "collective-permute"):
            assert collective not in text, collective
    print(f"{what} {placement}: temporaries "
          f"{mem.temp_size_in_bytes / 1e6:.1f} MB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB a device")


def test_parent_gc_fails_the_limit(set_aw_programs):
    """The GC as it stood (vmapped scatter) at [16, 65536]: whole snapshot
    fields re-laid out and back, 8 GB beside 8.87 GB of tables on a 16 GB
    chip — the RESOURCE_EXHAUSTED of PERF §7 fault 2."""
    compiled, _held = set_aw_programs["one_chip"]("parent_gc")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp > 8 * ROW_PROGRAM_TEMP_LIMIT, f"{temp / 1e9:.2f} GB"
    assert 8.87e9 + temp > 16e9
