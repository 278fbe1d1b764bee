import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers AND
compiles, and extract the roofline terms from the compiled artifact.

The two lines above MUST precede any other import (jax locks the device
count at first backend init); this module is the only place in the repo
that forces 512 host devices.

Per cell::

    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…) \
                      .lower(**input_specs(arch, shape))
        compiled = lowered.compile()
        print(compiled.memory_analysis())     # proves it fits (or not)
        print(compiled.cost_analysis())       # FLOPs/bytes for §Roofline

plus the collective-byte parse of the optimized HLO
(``analysis.hlo.collective_bytes``).  Results are appended to a JSONL
file consumed by ``benchmarks/bench_roofline.py`` and EXPERIMENTS.md.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
        --shape train_4k --mesh single --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo as hlo_mod
from repro.analysis.roofline import V5E_KIND, model_flops, roofline_report
from repro.configs import get_config
from repro.configs.archs import ASSIGNED
from repro.configs.base import ArchConfig
from repro.configs.shapes import SHAPES, ShapeCfg, applicable, enc_len_for
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.models.layers import axis_rules
from repro.models.transformer import TransformerLM
from repro.optim import adamw_init, adamw_update, microbatch_grads
from repro.train.trainer import TrainState

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# Step builders (abstract: everything flows through eval_shape / lower)
# ---------------------------------------------------------------------------

def _moment_dtype(cfg: ArchConfig):
    return DTYPES[cfg.opt_moment_dtype]


def abstract_state(lm: TransformerLM) -> Any:
    cfg = lm.cfg

    def make():
        p = lm.init(jax.random.PRNGKey(0))
        return TrainState(params=p, opt=adamw_init(p, _moment_dtype(cfg)))

    return jax.eval_shape(make)


def abstract_params(lm: TransformerLM) -> Any:
    return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))


def abstract_cache(lm: TransformerLM, batch: int, max_len: int,
                   cross_len: int) -> Any:
    return jax.eval_shape(
        lambda: lm.init_cache(batch, max_len, cross_len=cross_len))


def build_train_step(lm: TransformerLM, rules: Dict[str, Any],
                     n_micro: int, grad_specs=None):
    cfg = lm.cfg

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        with axis_rules(rules):
            loss, grads, metrics = microbatch_grads(
                lambda p, b: lm.loss(p, b), state.params, batch, n_micro,
                grad_specs=grad_specs)
            params, opt, om = adamw_update(
                state.params, grads, state.opt, lr=1e-4)
            metrics.update(om)
            metrics["loss"] = loss
        return TrainState(params=params, opt=opt), metrics

    return train_step


def build_prefill_step(lm: TransformerLM, rules: Dict[str, Any]):
    def prefill_step(params, batch: Dict[str, jax.Array]):
        with axis_rules(rules):
            frontend = batch.get("image_embeds", batch.get("frame_embeds"))
            return lm.prefill(params, batch["tokens"], frontend=frontend)

    return prefill_step


def build_serve_step(lm: TransformerLM, rules: Dict[str, Any]):
    def serve_step(params, cache, batch: Dict[str, jax.Array]):
        with axis_rules(rules):
            return lm.decode_step(params, cache, batch["tokens"],
                                  batch["positions"])

    return serve_step


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — zero allocation)
# ---------------------------------------------------------------------------

def cell_inputs(cfg: ArchConfig, shape: ShapeCfg) -> Dict[str, Any]:
    from repro.configs.shapes import input_specs
    return input_specs(cfg, shape)


def _sharded(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True, sp_override: Optional[bool] = None,
             n_micro_override: Optional[int] = None,
             fsdp_override: Optional[bool] = None,
             expert_axis_override: Optional[str] = None,
             keep_artifacts: bool = False,
             grad_spec: bool = False,
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = applicable(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mname = mesh_name(mesh)
    row: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mname,
                           "chips": mesh.size, "status": "skip",
                           "skip_reason": skip}
    if skip:
        return row

    sp = cfg.sp if sp_override is None else sp_override
    fsdp = cfg.fsdp if fsdp_override is None else fsdp_override
    expert_axis = cfg.expert_axis if expert_axis_override is None \
        else expert_axis_override
    n_micro = cfg.n_micro if n_micro_override is None else n_micro_override
    policy = shd.policy_for_mesh(mesh, fsdp=fsdp, sp=sp,
                                 expert_axis=expert_axis)
    rules = policy.rules(mesh)
    lm = TransformerLM(cfg)
    inputs = cell_inputs(cfg, shape)
    batch_specs = shd.batch_specs(policy, mesh,
                                  {k: v.shape for k, v in inputs.items()})
    t0 = time.time()
    try:
        with mesh:
            if shape.kind == "train":
                state = abstract_state(lm)
                pspecs = shd.param_specs(state.params, mesh, policy)
                sspecs = TrainState(params=pspecs,
                                    opt=dataclasses.replace(
                                        state.opt, step=P(), mu=pspecs,
                                        nu=pspecs))
                step = build_train_step(
                    lm, rules, n_micro,
                    grad_specs=pspecs if grad_spec else None)
                lowered = jax.jit(
                    step,
                    in_shardings=(_sharded(mesh, sspecs),
                                  _sharded(mesh, batch_specs)),
                    out_shardings=(_sharded(mesh, sspecs), None),
                    donate_argnums=(0,),
                ).lower(state, inputs)
            elif shape.kind == "prefill":
                params = abstract_params(lm)
                pspecs = shd.param_specs(params, mesh, policy)
                cross_len = cfg.cross_kv_len or (
                    enc_len_for(cfg, shape) if cfg.enc_dec else 0)
                cache = abstract_cache(lm, shape.global_batch, shape.seq_len,
                                       cross_len)
                cspecs = shd.cache_specs(cache, mesh, policy)
                step = build_prefill_step(lm, rules)
                lowered = jax.jit(
                    step,
                    in_shardings=(_sharded(mesh, pspecs),
                                  _sharded(mesh, batch_specs)),
                    out_shardings=(None, _sharded(mesh, cspecs)),
                ).lower(params, inputs)
            else:  # decode
                params = abstract_params(lm)
                pspecs = shd.param_specs(params, mesh, policy)
                cross_len = cfg.cross_kv_len or (
                    enc_len_for(cfg, shape) if cfg.enc_dec else 0)
                cache = abstract_cache(lm, shape.global_batch, shape.seq_len,
                                       cross_len)
                cspecs = shd.cache_specs(cache, mesh, policy)
                step = build_serve_step(lm, rules)
                lowered = jax.jit(
                    step,
                    in_shardings=(_sharded(mesh, pspecs),
                                  _sharded(mesh, cspecs),
                                  _sharded(mesh, batch_specs)),
                    out_shardings=(None, _sharded(mesh, cspecs)),
                    donate_argnums=(1,),
                ).lower(params, cache, inputs)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        # raw XLA numbers, list/dict-normalized (see caveat)
        from repro.analysis.hlo_cost import xla_cost_dict
        cost = xla_cost_dict(compiled)
        hlo_text = compiled.as_text()

        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        # Forward-only steps do ~2·N·D; training does ~6·N·D.
        flop_mult = 1.0 if shape.kind == "train" else (1.0 / 3.0)
        mf = model_flops(cfg.param_count(), tokens,
                         active_param_count=cfg.active_param_count()) \
            * flop_mult

        peak_mem = getattr(mem, "temp_size_in_bytes", 0) or 0
        arg_mem = getattr(mem, "argument_size_in_bytes", 0) or 0
        out_mem = getattr(mem, "output_size_in_bytes", 0) or 0
        alias_mem = getattr(mem, "alias_size_in_bytes", 0) or 0

        rep = roofline_report(
            arch=arch, shape=shape_name, mesh_name=mname, chips=mesh.size,
            hlo_text=hlo_text, model_flops_total=mf,
            device_kind=V5E_KIND,
            peak_memory_bytes=float(peak_mem + arg_mem + out_mem - alias_mem),
            arch_cfg=cfg, shape_cfg=shape, n_micro=n_micro)
        row.update(rep.row())
        row.update({
            "status": "ok",
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "collectives": rep.collective_detail,
            "xla_raw_flops": float(cost.get("flops", 0.0)),
            "xla_raw_bytes": float(cost.get("bytes accessed", 0.0)),
            "mem_temp": int(peak_mem), "mem_args": int(arg_mem),
            "mem_out": int(out_mem), "mem_alias": int(alias_mem),
            "policy": {"sp": sp, "fsdp": fsdp, "expert_axis": expert_axis,
                       "n_micro": n_micro, "grad_spec": grad_spec},
        })
        if keep_artifacts:
            from repro.analysis import hlo_cost as _hc
            row["_cost"] = _hc.analyze(hlo_text)     # not JSON-serializable
        if verbose:
            print(f"[ok] {arch} × {shape_name} × {mname}  "
                  f"compile={t_compile:.0f}s  "
                  f"t=(c {rep.t_compute:.4f}, m {rep.t_memory:.4f}, "
                  f"x {rep.t_collective:.4f})s  "
                  f"bound={rep.bottleneck}  mfu≤{rep.mfu_bound:.2f}  "
                  f"mem/dev={(peak_mem + arg_mem)/2**30:.2f}GiB")
            print("  memory_analysis:", mem)
            print("  hlo_cost (trip-aware): flops=%.3e bytes=%.3e"
                  % (rep.hlo_flops, rep.hlo_bytes))
            print("  xla cost_analysis (counts while bodies once): "
                  "flops=%.3e bytes=%.3e"
                  % (cost.get("flops", 0), cost.get("bytes accessed", 0)))
            print("  collectives:", rep.collective_detail)
    except Exception as e:  # noqa: BLE001 — every failure is a finding
        row["status"] = "error"
        row["error"] = f"{type(e).__name__}: {e}"
        row["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[ERR] {arch} × {shape_name} × {mname}: {row['error']}")
    return row


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ASSIGNED)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) cell")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--sp", type=int, default=None, help="override SP (0/1)")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--expert-axis", choices=["experts", "ff"], default=None)
    ap.add_argument("--grad-spec", action="store_true",
                    help="constrain grad accumulation to param sharding "
                         "(the §Perf reduce-scatter optimization)")
    args = ap.parse_args()

    cells = []
    archs = ASSIGNED if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for arch, shape, mp in cells:
            row = run_cell(arch, shape, multi_pod=mp,
                           sp_override=None if args.sp is None
                           else bool(args.sp),
                           n_micro_override=args.n_micro,
                           fsdp_override=None if args.fsdp is None
                           else bool(args.fsdp),
                           expert_axis_override=args.expert_axis,
                           grad_spec=args.grad_spec)
            f.write(json.dumps(row) + "\n")
            f.flush()
            jax.clear_caches()       # keep the 80-cell sweep's RSS bounded


if __name__ == "__main__":
    main()
