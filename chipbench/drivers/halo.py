"""COMB halo cells: the program's stencil on a process grid of chips.

Traffic keys:

    path            "fused"  -> jit(make_halo_fn(mesh, "overlap", steps))
                    "segmented" -> HaloProgram(mesh, explicit=True).run(...)
    engine          segmented: ProgressEngine mode, or null for none
    steps_per_call  stencil steps in one timed call
    sample_calls    window calls whose outputs are kept and checked,
                    drawn from the seed among the first 40 (the last
                    call's output is checked as well)
    trace_calls     calls in the traced window of a ``--trace 1`` run

Every call starts from the seeded field, so every call's answer is the
same and stays finite. ``check`` compares each kept output, every block
of it, with the plain periodic stencil computed in float32 on the chip
that holds the block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.refs.stencil import block_reference, widened_slab
from chipbench.work import least_time_s, stencil_step_bytes, stencil_step_flops
from repro.comm.halo import HaloProgram, make_halo_fn
from repro.comm.progress import ProgressEngine
from repro.core.collector import global_collector
from repro.core.compat import make_mesh

SAMPLE_RANGE = 40


def seed_key(seed: int):
    """A key that uses every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def process_grid(config: dict, chips: int) -> tuple:
    return tuple(config["process_grids"][str(chips)])


def make_field(seed: int, shape: tuple, sharding, dtype=jnp.float32):
    """The seeded field, made on the device in one call."""
    return jax.jit(lambda k: jax.random.normal(k, shape, dtype),
                   out_shardings=sharding)(seed_key(seed))


def build_program(traffic: dict, mesh, width: int):
    """The timed path: a callable ``u0 -> output after steps_per_call``
    and the progress engine it owns (or None)."""
    steps = traffic["steps_per_call"]
    if traffic["path"] == "fused":
        fn = jax.jit(make_halo_fn(mesh, width=width, variant="overlap",
                                  steps=steps))
        return fn, None
    prog = HaloProgram(mesh, width=width, explicit=True)
    engine = (ProgressEngine(traffic["engine"]) if traffic.get("engine")
              else None)
    return functools.partial(prog.run, steps=steps, engine=engine), engine


@functools.lru_cache(maxsize=None)
def _reference_fn(steps: int, dtype: str):
    def ref(slab):
        out = block_reference(slab.astype(dtype), steps, xp=jnp)
        return out.astype(jnp.float32)
    return jax.jit(ref)


def reference_block(field: np.ndarray, index: tuple, device, steps: int,
                    dtype: str = "float32"):
    """The block ``index`` of the answer, computed in ``dtype`` on
    ``device`` from its widened slab of the initial field."""
    slab = jax.device_put(widened_slab(field, index, steps), device)
    return _reference_fn(steps, dtype)(slab)


def max_error(blocks, field: np.ndarray, steps: int) -> float:
    """max |block - reference| over max |reference|, over ``blocks``
    given as (index, device, data); each block's float32 reference is
    computed once, on its chip, for every block at that place."""
    places = {}
    for index, device, data in blocks:
        key = (tuple((s.start, s.stop) for s in index), device.id)
        places.setdefault(key, (index, device, []))[2].append(data)
    err, scale = 0.0, 0.0
    for index, device, datas in places.values():
        ref = reference_block(field, index, device, steps)
        for data in datas:
            err = max(err, float(jnp.max(jnp.abs(data - ref))))
        scale = max(scale, float(jnp.max(jnp.abs(ref))))
        del ref
    return err / scale


def shard_blocks(arrays):
    return [(s.index, s.device, s.data) for a in arrays
            for s in a.addressable_shards]


def control_readings(ctx) -> dict:
    """The control: the reference computed in bfloat16, the precision
    below the configuration's float32, put in the program's place."""
    cfg = ctx.config
    dims = process_grid(cfg, len(ctx.devices))
    mesh = make_mesh(dims, ("x", "y", "z"), devices=ctx.devices)
    shape = tuple(d * cfg["box"] for d in dims)
    u0 = make_field(ctx.seed, shape, NamedSharding(mesh, P("x", "y", "z")))
    field = np.asarray(u0)
    steps = ctx.traffic["steps_per_call"]
    blocks = [(s.index, s.device,
               reference_block(field, s.index, s.device, steps, "bfloat16"))
              for s in u0.addressable_shards]
    del u0
    return {"control_bf16": {"halo_max_err_rel":
                             max_error(blocks, field, steps)}}


class HaloSession:
    def __init__(self, ctx):
        cfg, tr = ctx.config, ctx.traffic
        self.steps = tr["steps_per_call"]
        self.limit = cfg["limits"]["halo_max_err_rel"]
        box, width = cfg["box"], cfg["ghost_width"]
        dims = process_grid(cfg, len(ctx.devices))
        self.mesh = make_mesh(dims, ("x", "y", "z"), devices=ctx.devices)
        shape = tuple(d * box for d in dims)
        self.u0 = make_field(ctx.seed, shape,
                             NamedSharding(self.mesh, P("x", "y", "z")))
        self.run, self.engine = build_program(tr, self.mesh, width)
        rng = np.random.default_rng(ctx.seed)
        self.sample = set(rng.choice(SAMPLE_RANGE, tr["sample_calls"],
                                     replace=False).tolist())
        self.kept, self.last, self.n = [], None, 0
        for _ in range(2):                 # compile, then one warm call
            jax.block_until_ready(self.run(self.u0))
        least, bound = None, None
        if ctx.peaks:
            itemsize = np.dtype(cfg["dtype"]).itemsize
            least, bound = least_time_s(
                stencil_step_flops(box),
                stencil_step_bytes(box, width, itemsize), ctx.peaks)
        self.extras = {"least_step_s": least, "least_bound": bound}

    def begin_window(self):
        global_collector().drain()

    def end_window(self):
        return global_collector().drain()

    def call(self):
        out = jax.block_until_ready(self.run(self.u0))
        if self.n in self.sample:
            self.kept.append(out)
        self.last = out
        self.n += 1
        return {"steps": self.steps}

    def check(self):
        if self.engine is not None:
            self.engine.shutdown()
        outputs = self.kept + [self.last]
        self.kept, self.last, self.run = [], None, None
        field = np.asarray(self.u0)
        self.u0 = None
        err = max_error(shard_blocks(outputs), field, self.steps)
        return [("halo_max_err_rel", err, self.limit)]


def build(ctx):
    return HaloSession(ctx)
