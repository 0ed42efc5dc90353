import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any other import (jax locks the device
count at first init) — 512 placeholder host devices back the production
meshes:

* single-pod: 16 x 16  ("data", "model")        = 256 chips
* multi-pod:  2 x 16 x 16 ("pod","data","model") = 512 chips

For each cell this script jits the real step function (train_step with
optimizer update + microbatching + remat for train shapes; serve_step
with donated KV/recurrent state for decode shapes; prefill forward for
prefill shapes) against ShapeDtypeStruct inputs — no arrays are ever
allocated — then runs ``.lower()``, ``.compile()``, and records:

* ``compiled.memory_analysis()``   (per-device bytes: proves it fits)
* ``compiled.cost_analysis()``     (HLO FLOPs / bytes for the roofline)
* collective bytes parsed from the optimized HLO (all-gather,
  all-reduce, reduce-scatter, all-to-all, collective-permute)

Results stream to JSON for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

One chip's share of a deployment (``--one-chip``: a 1 x 1 mesh) at its
own batch and cache, with ModelConfig overrides (``moe`` takes a dict
of MoEConfig fields), e.g. a DeepSeek-V2-Lite pipeline stage of 9
layers holding 8 of each layer's 64 experts, served with PIM FFNs:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-v2-lite \
      --shape decode_32k --one-chip --batch 32 --seq-len 4096 \
      --dtype float32 \
      --override '{"n_layers": 9, "moe": {"experts_held": 8},
                   "pim_linear_mode": "pim", "pim_block_mode": "ffn"}'
A decode cell's ``per_device`` gives the decode state's bytes
(``state_bytes``: KV caches, latents, recurrent state) beside the float
weights a PIM serve step keeps beside its plan (``kept_float_bytes``).
"""
import argparse
import dataclasses
import functools
import json
import math
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import ARCHS, SHAPES, get_config, shape_applicable

# No logging side effects at import time: handlers attach only when
# main() calls obs.setup_logging() (see repro.obs.logging).
log = obs.get_logger("dryrun")
from repro.launch.mesh import make_one_chip_mesh, make_production_mesh
from repro.models.model import build_model, input_specs
from repro.optim.adamw import AdamWConfig, OptState
from repro.train.sharding import (batch_shardings, param_shardings,
                                  state_shardings)
from repro.train.step import make_serve_step, make_train_step

# Per-shape microbatch counts (gradient accumulation) keeping one
# microbatch's activations within the per-chip HBM budget.
# PERF(H2): wide/deep archs (granite 52L x 6144) need more accumulation
# steps; MoE archs prefer fewer, larger chunks (dispatch efficiency).
import os as _os
MICROBATCHES = {"train_4k": int(_os.environ.get("MB", "8"))}
MICROBATCHES_BY_ARCH = {
    ("granite-20b", "train_4k"): 16,
    ("deepseek-moe-16b", "train_4k"): 16,
    ("phi3.5-moe-42b-a6.6b", "train_4k"): 16,
}

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n]*?\s+(\S+?)\[([0-9,]*)\]")
SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-operand bytes of every collective op in optimized HLO."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^[%\w.\-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        cm = re.search(r"\b(all-gather|all-reduce|reduce-scatter|"
                       r"all-to-all|collective-permute)(-start)?\(", rhs)
        if not cm:
            continue
        kind = cm.group(1)
        # result shape(s) are at the start of the rhs: possibly a tuple
        head = rhs.split(cm.group(0))[0]
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(head):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
    return out


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB", "PB"):
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}EB"


def configure(arch: str, overrides: Optional[Dict[str, Any]] = None):
    """``arch``'s config with ModelConfig ``overrides`` (``moe``: a dict
    of MoEConfig fields)."""
    cfg = get_config(arch)
    over = dict(overrides or {})
    if isinstance(over.get("moe"), dict):
        over["moe"] = dataclasses.replace(cfg.moe, **over["moe"])
    return dataclasses.replace(cfg, **over) if over else cfg


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True, one_chip: bool = False,
               batch: Optional[int] = None, seq_len: Optional[int] = None,
               overrides: Optional[Dict[str, Any]] = None,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    cfg = configure(arch, overrides)
    shape = next(s for s in SHAPES if s.name == shape_name)
    shape = dataclasses.replace(shape, global_batch=batch or
                                shape.global_batch,
                                seq_len=seq_len or shape.seq_len)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}
    mesh = (make_one_chip_mesh() if one_chip
            else make_production_mesh(multi_pod=multi_pod))
    t0 = time.time()
    model = build_model(cfg, remat=(shape.kind == "train"))
    state = 0
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    specs = input_specs(cfg, shape)
    params_like = jax.eval_shape(lambda k: model.init(k, dtype),
                                 jax.random.PRNGKey(0))
    ps = param_shardings(mesh, params_like)
    params_like = jax.tree.map(
        lambda s_, sh: jax.ShapeDtypeStruct(s_.shape, s_.dtype, sharding=sh),
        params_like, ps)

    kept = 0
    if shape.kind == "train":
        from repro.optim.adamw import adamw_init
        mb = MICROBATCHES_BY_ARCH.get((arch, shape.name),
                                      MICROBATCHES.get(shape.name, 1))
        train_step, _, jit_for = make_train_step(
            model, AdamWConfig(), mesh, microbatches=mb)
        from repro.train.sharding import zero1_shardings
        opt_like = jax.eval_shape(adamw_init, params_like)
        zs = zero1_shardings(mesh, params_like)
        os_sh = OptState(m=zs, v=zs,
                         count=jax.sharding.NamedSharding(
                             mesh, jax.sharding.PartitionSpec()))
        opt_like = jax.tree.map(
            lambda s_, sh: jax.ShapeDtypeStruct(s_.shape, s_.dtype,
                                                sharding=sh),
            opt_like, os_sh)
        batch_like = dict(specs)
        bs = batch_shardings(mesh, batch_like)
        batch_like = jax.tree.map(
            lambda s_, sh: jax.ShapeDtypeStruct(s_.shape, s_.dtype,
                                                sharding=sh),
            batch_like, bs)
        jitted = jit_for(params_like, batch_like)
        lowered = jitted.lower(params_like, opt_like, None, batch_like)
    elif shape.kind == "prefill":
        from repro.train.step import make_prefill
        prefill, jit_for = make_prefill(model, mesh)
        batch_like = dict(specs)
        bs = batch_shardings(mesh, batch_like)
        batch_like = jax.tree.map(
            lambda s_, sh: jax.ShapeDtypeStruct(s_.shape, s_.dtype,
                                                sharding=sh),
            batch_like, bs)
        jitted = jit_for(params_like, batch_like)
        lowered = jitted.lower(params_like, batch_like)
    else:  # decode
        serve_step, jit_for = make_serve_step(model, mesh)
        states_like = jax.eval_shape(
            lambda: model.init_decode_state(shape.global_batch,
                                            shape.seq_len, dtype))
        ss = state_shardings(mesh, states_like)
        states_like = jax.tree.map(
            lambda s_, sh: jax.ShapeDtypeStruct(s_.shape, s_.dtype,
                                                sharding=sh),
            states_like, ss)
        batch_like = {
            k: jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=batch_shardings(mesh, {k: v})[k])
            for k, v in specs.items()}
        jitted = jit_for(params_like, states_like, batch_like)
        lowered = jitted.lower(params_like, states_like,
                               batch_like["token"], batch_like["position"])
        kept = _kept_float_bytes(model.cfg, params_like)
        state = _shard_bytes(states_like)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # jax returns one dict, a list of per-executable dicts, or None
    # depending on version/backend — normalize to a single dict.
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    elif cost is None:
        cost = {}
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "1x1" if one_chip else "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", -1.0),
        "bytes_accessed": cost.get("bytes accessed", -1.0),
        "per_device": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "kept_float_bytes": kept,
            "state_bytes": state,
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)
                           + kept),
        },
        "collective_bytes": coll,
    }
    if verbose:
        pd = rec["per_device"]
        print(f"  [{rec['mesh']}] {arch} x {shape_name}: "
              f"flops={rec['flops']:.3e} "
              f"args={_fmt_bytes(pd['argument_bytes'])} "
              f"temp={_fmt_bytes(pd['temp_bytes'])} "
              f"coll={ {k: _fmt_bytes(v) for k, v in coll.items()} } "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)",
              flush=True)
    return rec


def _shard_bytes(tree) -> int:
    """Per-device bytes of the sharded ShapeDtypeStructs in ``tree``."""
    return sum(math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize
               for s in jax.tree.leaves(tree))


def _kept_float_bytes(cfg, params_like) -> int:
    """Per-device bytes of the float weights a serve step keeps beside
    their plan: the step's arguments hold the plan, and ``ServeStep``
    the params it planned from."""
    from repro.models.transformer import plan_weights
    from repro.pim import PlannedWeight

    def is_plan(w):
        return isinstance(w, PlannedWeight)

    plan = jax.eval_shape(functools.partial(plan_weights, cfg), params_like)
    planned = {path for path, w in jax.tree_util.tree_flatten_with_path(
        plan, is_leaf=is_plan)[0] if is_plan(w)}
    return _shard_bytes([s for path, s in jax.tree_util.tree_flatten_with_path(
        params_like)[0] if path in planned])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--one-chip", action="store_true",
                    help="a 1 x 1 mesh: one chip's share")
    ap.add_argument("--batch", type=int, default=None,
                    help="the shape's global batch, overridden")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="the shape's sequence (cache) length, overridden")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"),
                    help="weights' and decode state's dtype")
    ap.add_argument("--override", default="",
                    help="JSON dict of ModelConfig overrides; 'moe' takes "
                         "a dict of MoEConfig fields")
    args = ap.parse_args()
    cell_kw = dict(one_chip=args.one_chip, batch=args.batch,
                   seq_len=args.seq_len,
                   overrides=json.loads(args.override or "{}"),
                   dtype=jnp.dtype(args.dtype))
    obs.setup_logging()

    cells = []
    if args.all:
        for arch, cfg in ARCHS.items():
            for s in SHAPES:
                cells.append((arch, s.name))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    failed = 0
    for arch, shp in cells:
        for mp in meshes:
            try:
                results.append(lower_cell(arch, shp, multi_pod=mp,
                                          **cell_kw))
            except Exception as e:   # noqa: BLE001
                failed += 1
                traceback.print_exc()
                results.append({"arch": arch, "shape": shp,
                                "mesh": "2x16x16" if mp else "16x16",
                                "status": "error", "error": str(e)})
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skipped")
    log.info("dry-run: %d ok, %d skipped, %d failed -> %s",
             n_ok, n_skip, failed, args.out)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
