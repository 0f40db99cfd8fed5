"""Training cells: the program's jitted train step on a seeded model.

Set-up builds one object, the compiled step with its parameters and
AdamW state, and drives it from the seed through its first
``checked_steps`` steps by the window's own ``call``; the window then
goes on with the same object. Per call, as ``launch/train.main`` does:
the batch is built on the host, the step runs, and its loss is read.

The weights are the benchmark's, made on the device in one call from
the seed (``make_params``), so the reference can take the same ones
without taking anything the program made. Set-up keeps two tensors of
the program's on the host: the first clipped gradient as AdamW got it,
read back from its first moment, and the change of the parameters over
the checked steps. ``check`` frees the program's state, runs the float32
reference (``refs/xlstm.py``) over the same first batches, and reads,
a leaf being one layer's slice of a parameter,

    loss_gap           max over the checked steps of |loss - ref| / |ref|
    grad_err           median over leaves of |g - g_ref| / |g_ref|
    grad_err_embed     the same for the embedding, and ``_head`` for the
    grad_err_head      output head: the leaves half a batch moves most
    change_err         median over leaves of the same for the change,
    change_err_embed   leaving out leaves whose reference gradient is
    change_err_head    under a thousandth of the median leaf's

The configuration gives a limit to each number in ``COMPARED``; the
others are printed beside them (PERF.md says why these).

Traffic keys: batch, seq_len, n_successors, checked_steps,
reference_rows_per_block, trace_calls, optimizer (AdamW settings).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.drivers.halo import seed_key
from chipbench.refs import xlstm as ref
from chipbench.traffic_gen import TokenStream
from repro.configs.archs import get_config
from repro.configs.base import LayerSpec
from repro.core.compat import make_mesh
from repro.models import model as M
from repro.optim import adamw
from repro.sharding import rules as R
from repro.train.step import make_train_step

EXCLUDE_BELOW = 1e-3          # of the median leaf's reference gradient
# the numbers that decide ``correct``; the configuration limits each
COMPARED = ("loss_gap", "grad_err", "grad_err_head", "change_err",
            "change_err_head")


def model_config(config: dict):
    """The program's configuration of the file's model: the program's
    ``arch`` preset with every size, the layer pattern and the xLSTM
    block settings taken from the file."""
    base = get_config(config["arch"])
    spec = dataclasses.replace(
        base.xlstm, proj_factor_mlstm=config["proj_factor_mlstm"],
        proj_factor_slstm=config.get("proj_factor_slstm",
                                     base.xlstm.proj_factor_slstm),
        conv_kernel=config["conv_kernel"], chunk=config["chunk"])
    pattern = tuple(LayerSpec(mixer=m, ffn="none")
                    for m in config["pattern"])
    return dataclasses.replace(
        base, n_layers=config["n_layers"], d_model=config["d_model"],
        n_heads=config["n_heads"], n_kv_heads=config["n_heads"],
        vocab_size=config["vocab_size"], norm_eps=config["norm_eps"],
        act=config["act"], dtype=config["dtype"], remat=config["remat"],
        pattern=pattern, xlstm=spec)


def _stacked(path) -> bool:
    return str(path[0].key).startswith("pos")


def make_params(seed: int, shapes, shardings):
    """Seeded parameters with the shapes and shardings of the program's
    tree, made on the device in one call: normal, scaled by 1/sqrt of
    the fan-in for matrices and 0.1 for vectors (a layer stack's leading
    dimension is neither)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        out = []
        for i, (path, s) in enumerate(flat):
            shape = s.shape[1:] if _stacked(path) else s.shape
            std = 1.0 / math.sqrt(shape[-2]) if len(shape) >= 2 else 0.1
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, s.shape, jnp.float32)
                        * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


@jax.jit
def leaf_norms(tree):
    """Norm of every leaf, per layer slice for stacked leaves."""
    def norm(path, x):
        x = x.astype(jnp.float32)
        if _stacked(path):
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))[None]
    return jax.tree_util.tree_map_with_path(norm, tree)


@jax.jit
def _diff(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32)
                        - y.astype(jnp.float32), a, b)


@jax.jit
def _scaled(tree, factor):
    return jax.tree.map(lambda x: x * factor, tree)


def to_host(tree):
    """The tree's leaves as numpy arrays, all copies started at once."""
    for x in jax.tree.leaves(tree):
        x.copy_to_host_async()
    return jax.device_get(tree)


def flat_norms(tree) -> dict:
    """{leaf name: norm} from ``leaf_norms`` output, on the host."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(p.key) for p in path)
        v = np.asarray(v, np.float64)
        for i, x in enumerate(v):
            out[f"{name}[{i}]" if len(v) > 1 else name] = float(x)
    return out


def leaf_errors(prog, ref) -> tuple:
    """({leaf: |prog - ref| / |ref|}, {leaf: |ref|}) of two trees, the
    first on the host or the device."""
    prog, ref = jax.device_put((prog, ref))
    refn = flat_norms(leaf_norms(ref))
    dist = flat_norms(leaf_norms(_diff(prog, ref)))
    err = {k: dist[k] / refn[k] if refn[k] > 0 else
           (0.0 if dist[k] == 0 else math.inf) for k in refn}
    return err, refn


def worst(d: dict, n: int = 3) -> list:
    return sorted(d.items(), key=lambda kv: -kv[1])[:n]


def gaps(prog: dict, refr: dict) -> list:
    """The numbers read from a run's readings against the reference's,
    each a dict with ``losses`` and the trees ``grad`` and ``change``, as
    (name, value)."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], refr["losses"]))
    g_err, g_ref = leaf_errors(prog["grad"], refr["grad"])
    c_err, _ = leaf_errors(prog["change"], refr["change"])
    floor = EXCLUDE_BELOW * float(np.median(list(g_ref.values())))
    c_moved = {k: v for k, v in c_err.items() if g_ref[k] >= floor}
    print(f"worst grad_err leaves {worst(g_err)}; worst change_err leaves "
          f"{worst(c_moved)}; {len(c_err) - len(c_moved)} of {len(c_err)} "
          f"leaves left out of the change", file=sys.stderr)
    median = lambda d: float(np.median(list(d.values())))
    return [("loss_gap", loss),
            ("grad_err", median(g_err)),
            ("grad_err_embed", g_err["embed"]),
            ("grad_err_head", g_err["lm_head"]),
            ("change_err", median(c_moved)),
            ("change_err_embed", c_err["embed"]),
            ("change_err_head", c_err["lm_head"])]


def reference_readings(seed: int, cfg_json: dict, tr: dict, data,
                       shapes, shardings, mode: str = "f32",
                       rows_kept: int = None) -> dict:
    """Readings of the reference over the checked steps' batches, from
    the seeded weights. ``mode="fp8"`` is the control; ``rows_kept`` (the
    loss is the mean over only the first rows of every batch) plants a
    fault in the reference put in the program's place."""
    batches = []
    for s in range(tr["checked_steps"]):
        tokens, labels = data.batch_at(s)
        batches.append((jnp.asarray(tokens[:rows_kept]),
                        jnp.asarray(labels[:rows_kept])))
    with jax.default_matmul_precision("highest"):
        out = ref.train(make_params(seed, shapes, shardings), batches,
                        cfg_json, tr["optimizer"], mode,
                        tr["reference_rows_per_block"])
    return {"losses": out["losses"], "grad": out["first_grad"],
            "change": _diff(out["params"],
                            make_params(seed, shapes, shardings))}


class TrainSession:
    def __init__(self, ctx):
        cfg_json, tr = ctx.config, ctx.traffic
        self.cfg_json, self.tr, self.seed = cfg_json, tr, ctx.seed
        self.cfg = model_config(cfg_json)
        self.opt = dict(tr["optimizer"])
        mesh = make_mesh((len(ctx.devices), 1), ("data", "model"),
                         devices=ctx.devices)
        rules = R.make_rules(mesh)
        self.shapes = M.param_shapes(self.cfg)
        self.pshard = R.tree_shardings(M.param_axes(self.cfg), mesh, rules,
                                       self.shapes)
        oshard = {"m": self.pshard, "v": self.pshard,
                  "step": NamedSharding(mesh, P())}
        self.bshard = NamedSharding(mesh, P("data", None))
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(R.sharding_context(mesh, rules))
        self.params = make_params(ctx.seed, self.shapes, self.pshard)
        self.state = jax.jit(adamw.init_state, out_shardings=oshard)(
            self.params)
        step = make_train_step(self.cfg, adamw.AdamWConfig(**self.opt))
        self.step_fn = jax.jit(step, in_shardings=(self.pshard, oshard,
                                                   self.bshard),
                               out_shardings=(self.pshard, oshard, None),
                               donate_argnums=(0, 1))
        self.data = TokenStream(ctx.seed, tr["batch"], tr["seq_len"],
                                self.cfg.vocab_size, tr["n_successors"])
        self.n, self.losses = 0, []
        self.tokens = tr["batch"] * tr["seq_len"]
        self.readings = self._first_steps(tr["checked_steps"])
        fl = importlib.import_module("chipbench.flops." + cfg_json["flops"])
        self.extras = {"flops_per_token": fl.train_flops_per_token(
            cfg_json, tr["seq_len"])}

    def _feed(self):
        tokens, labels = self.data.batch_at(self.n)
        return {"tokens": jax.device_put(tokens, self.bshard),
                "labels": jax.device_put(labels, self.bshard)}

    def call(self):
        with jax.profiler.TraceAnnotation("chipbench.feed"):
            batch = self._feed()
        self.params, self.state, metrics = self.step_fn(
            self.params, self.state, batch)
        with jax.profiler.TraceAnnotation("chipbench.loss_read"):
            loss = float(metrics["loss"])
        self.n += 1
        self.losses.append(loss)
        return {"tokens": self.tokens}

    def _first_steps(self, steps: int) -> dict:
        for i in range(steps):
            self.call()
            if i == 0:
                grad = to_host(_scaled(self.state["m"],
                                       1.0 / (1 - self.opt["b1"])))
        p0 = make_params(self.seed, self.shapes, self.pshard)
        change = to_host(_diff(self.params, p0))
        del p0
        return {"losses": list(self.losses[:steps]), "grad": grad,
                "change": change}

    def free_program(self):
        self.params = self.state = self.step_fn = None
        self._stack.close()

    def check(self):
        limits = self.cfg_json.get("limits", {})
        if set(limits) != set(COMPARED):
            raise ValueError(f"the configuration's limits must be exactly "
                             f"{COMPARED}; it has {limits}")
        self.free_program()
        refr = reference_readings(self.seed, self.cfg_json, self.tr,
                                  self.data, self.shapes, self.pshard)
        read = gaps(self.readings, refr)
        self.readings = None
        print(f"readings {dict(read)}", file=sys.stderr)
        return [(name, value, limits[name]) for name, value in read
                if name in COMPARED]


def control_readings(ctx) -> dict:
    """The program's own readings (``sound``: set-up and the checked
    steps of a run, no window), the control (the reference in fp8) and
    the faults planted in the reference put in the program's place, each
    read against the float32 reference. Half a batch is a run of its own;
    the other faults are made from the reference's own readings: a state
    left unchanged (no gradient in the first moment, no change), one
    element of the output head moved by 1.0 (``altered``), and the update
    applied with its sign reversed."""
    session = TrainSession(ctx)
    session.free_program()
    args = (ctx.seed, session.cfg_json, session.tr, session.data,
            session.shapes, session.pshard)
    base = reference_readings(*args)
    base = dict(base, grad=to_host(base["grad"]),
                change=to_host(base["change"]))
    change = base["change"]
    head = change["lm_head"].copy()
    head[0, 0] += 1.0
    zeros = lambda t: jax.tree.map(np.zeros_like, t)
    variants = {     # each made and read in turn, so one is on the device
        "control_fp8": lambda: reference_readings(*args, mode="fp8"),
        "fault_half_batch": lambda: reference_readings(
            *args, rows_kept=session.tr["batch"] // 2),
        "fault_unchanged": lambda: dict(base, grad=zeros(base["grad"]),
                                        change=zeros(change)),
        "fault_altered": lambda: dict(base, change=dict(change,
                                                        lm_head=head)),
        "fault_sign": lambda: dict(base, change=jax.tree.map(np.negative,
                                                             change)),
    }
    out = {"sound": dict(gaps(session.readings, base))}
    for name, make in variants.items():
        out[name] = dict(gaps(make(), base))
    return out


def build(ctx):
    return TrainSession(ctx)
