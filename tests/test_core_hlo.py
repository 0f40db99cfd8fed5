"""HLO parsing: collective extraction, byte accounting, trip-count walk —
on canned modules and on a real compiled sharded module (subprocess)."""
import textwrap

from repro.core import hlo, hlo_cost

CANNED = textwrap.dedent("""\
    HloModule test, is_scheduled=true

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    %body (arg: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
      %arg = (s32[], f32[128,256]) parameter(0)
      %i = s32[] get-tuple-element(%arg), index=0
      %x = f32[128,256] get-tuple-element(%arg), index=1
      %w = f32[256,256] constant({...})
      %dot = f32[128,256] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[128,256] all-reduce(%dot), replica_groups=[4,2]<=[8], use_global_device_ids=true, to_apply=%add
      ROOT %t = (s32[], f32[128,256]) tuple(%i, %ar)
    }

    %cond (arg: (s32[], f32[128,256])) -> pred[] {
      %arg = (s32[], f32[128,256]) parameter(0)
      ROOT %p = pred[] constant(true)
    }

    ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
      %p0 = f32[128,256] parameter(0)
      %ag = f32[128,512] all-gather(%p0), replica_groups={{0,1},{2,3}}, dimensions={1}
      %big = (s32[], f32[128,256],
        /*index=2*/ f32[1,1]) tuple(%p0, %p0, %p0)
      %init = (s32[], f32[128,256]) tuple(%p0, %p0)
      %w = (s32[], f32[128,256]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
      ROOT %out = f32[128,256] get-tuple-element(%w), index=1
    }
    """)


def test_collective_parsing_canned():
    ops = hlo.parse_collectives(CANNED)
    kinds = sorted(o.opcode for o in ops)
    assert kinds == ["all-gather", "all-reduce"]
    ar = [o for o in ops if o.opcode == "all-reduce"][0]
    assert ar.operand_bytes == 128 * 256 * 4
    assert ar.group_size == 2           # [4,2]<=[8]: 4 groups of 2
    ag = [o for o in ops if o.opcode == "all-gather"][0]
    assert ag.group_size == 2
    # ring wire bytes: all-gather moves out*(g-1)/g
    assert ag.wire_bytes == int(128 * 512 * 4 * (2 - 1) / 2)


def test_trip_count_walk():
    mc = hlo_cost.module_cost(CANNED)
    assert 12 in mc.trip_counts
    # dot flops: 2*128*256*256 per trip, 12 trips
    assert mc.flops == 2 * 128 * 256 * 256 * 12
    # all-reduce counted 12x, all-gather once
    assert mc.collective_count == 13


def test_multiline_joining():
    lines = hlo.logical_lines(CANNED)
    joined = [l for l in lines if "%big" in l]
    assert len(joined) == 1
    assert "tuple(" in joined[0]


def test_symbol_table_resolution():
    table = hlo.symbol_table(CANNED)
    assert table["dot"] == "f32[128,256]"
    assert table["p0"] == "f32[128,256]"


def test_op_histogram():
    hist = hlo.op_histogram(CANNED)
    assert hist["dot"] == 1
    assert hist["tuple"] >= 2


SCOPED = textwrap.dedent("""\
    HloModule jit_local, is_scheduled=true

    %fused_computation (p: f32[8,8]) -> f32[8,8] {
      %p = f32[8,8] parameter(0)
      ROOT %add.3 = f32[8,8] add(%p, %p), metadata={op_name="jit(local)/halo.interior/add" source_file="halo.py" source_line=77}
    }

    ENTRY %main (u: f32[8,8]) -> f32[8,8] {
      %u = f32[8,8] parameter(0), metadata={op_name="u"}
      %cp = (f32[1,8], f32[1,8],
        u32[]) collective-permute-start(%u), source_target_pairs={{0,1},{1,0}}, metadata={op_type="ppermute" op_name="jit(local)/halo.exchange/comm_ppermute_x/ppermute"}
      %copy-start = (f32[8,8], f32[8,8], u32[]) copy-start(%u), cross_program_prefetch_index=0
      ROOT %fusion.4 = f32[8,8] fusion(%u), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(local)/halo.interior/add" source_file="halo.py" source_line=77}
    }
    """)


def test_op_scopes_canned():
    scopes = hlo.op_scopes(SCOPED)
    assert scopes == {
        "add.3": "jit(local)/halo.interior/add",
        "u": "u",
        "cp": "jit(local)/halo.exchange/comm_ppermute_x/ppermute",
        "fusion.4": "jit(local)/halo.interior/add",
    }
    assert "copy-start" not in scopes      # no metadata, no scope


def test_op_scopes_of_the_halo_program():
    """Every halo program carries its four scopes into the compiled
    module's metadata, on the CPU backend too."""
    import jax
    import jax.numpy as jnp

    from repro.comm.halo import HaloProgram, make_halo_fn
    from repro.core.compat import make_mesh

    mesh = make_mesh((1, 1, 1), ("x", "y", "z"), devices=jax.devices()[:1])
    u = jnp.zeros((8, 8, 8), jnp.float32)

    def scopes(fn, *args):
        names = hlo.op_scopes(fn.lower(*args).compile().as_text()).values()
        return {part for name in names for part in name.split("/")
                if part.startswith("halo.")}

    want = {"halo.extract", "halo.exchange", "halo.interior",
            "halo.boundary"}
    for variant in ("overlap", "blocking"):
        fused = jax.jit(make_halo_fn(mesh, variant=variant, steps=2))
        assert scopes(fused, u) <= want
        assert {"halo.interior", "halo.boundary"} <= scopes(fused, u)
    prog = HaloProgram(mesh, explicit=True)
    faces = prog.extract(u)
    halos = prog.exchange(faces)
    assert scopes(prog.extract, u) == {"halo.extract"}
    assert scopes(prog.exchange, faces) == {"halo.exchange"}
    assert scopes(prog.interior, u) == {"halo.interior"}
    assert scopes(prog.boundary, u, u, halos) == {"halo.boundary"}


def test_real_sharded_module(subproc):
    out = subproc(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        def f(x, w):
            y = jnp.einsum('bd,df->bf', x, w)
            return jnp.einsum('bf,df->bd', y, w)
        xs = jax.ShapeDtypeStruct((64, 512), jnp.float32)
        ws = jax.ShapeDtypeStruct((512, 2048), jnp.float32)
        c = jax.jit(f,
            in_shardings=(NamedSharding(mesh, P("data", None)),
                          NamedSharding(mesh, P(None, "model"))),
            out_shardings=NamedSharding(mesh, P("data", None))).lower(xs, ws).compile()
        from repro.core import hlo
        ops = hlo.parse_collectives(c.as_text())
        ar = [o for o in ops if o.opcode == "all-reduce"]
        assert ar, "expected an all-reduce from the contraction"
        # per-device partial is (32, 512) f32
        assert ar[0].operand_bytes == 32 * 512 * 4, ar[0].operand_bytes
        assert ar[0].group_size == 4
        print("OK")
    """), devices=8)
    assert "OK" in out
