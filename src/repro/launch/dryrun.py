"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This proves the distribution plan is coherent without hardware: ShapeDtype-
Struct inputs (no allocation), jit with explicit in/out shardings, then
`.lower().compile()` on the mandated production mesh. Artifacts (memory
analysis, cost analysis, collective traffic from the partitioned HLO) are
written as JSON, one file per cell, for §Roofline / §Perf.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""
from __future__ import annotations

# The 512 placeholder devices MUST be requested before jax initializes —
# before any other import, including `from repro...` (jax locks the device
# count on first init). Keep these as the first executable lines.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..configs import ALL_ARCHS, SHAPES, get_config, shape_applicable
from ..models import model as model_lib
from ..models.param import values_of
from ..models.inputs import batch_struct
from ..sharding.planner import make_plan, plan_context
from ..train.optimizer import make_optimizer
from ..train.train_step import make_train_step, TrainState
from .mesh import make_production_mesh
from .hlo_analyzer import analyze

# tokens per device per microbatch for train_4k (bounds activation memory)
PER_DEVICE_MICRO = {
    "deepseek-coder-33b": 1, "arctic-480b": 1,
    "mistral-nemo-12b": 2, "chatglm3-6b": 2, "zamba2-7b": 2,
    "gemma2-2b": 4, "olmoe-1b-7b": 2, "paligemma-3b": 4,
    "mamba2-2.7b": 2, "hubert-xlarge": 4,
}


def n_microbatches(arch: str, global_batch: int, batch_div: int) -> int:
    pdm = PER_DEVICE_MICRO.get(arch, 2)
    n = max(global_batch // (pdm * batch_div), 1)
    while global_batch % n or (global_batch // n) % batch_div:
        n -= 1
    return max(n, 1)


def model_flops_analytic(cfg, shape_name: str) -> float:
    """MODEL_FLOPS per the brief: 6·N·D train / 2·N·D inference (N = active
    params sans embedding table), plus the attention-core term."""
    seq, batch, kind = SHAPES[shape_name]
    from ..models.transformer import padded_vocab
    n_eff = cfg.active_param_count() - padded_vocab(cfg) * cfg.d_model
    # attention core flops per token at context S: 4 * H * Dh * S (QK^T + AV)
    if cfg.family == "hybrid":
        n_attn_layers = cfg.n_layers // cfg.hybrid.shared_attn_every
    elif cfg.family == "ssm":
        n_attn_layers = 0
    else:
        n_attn_layers = cfg.n_layers
    attn_per_tok_ctx = 4 * cfg.n_heads * cfg.head_dim * n_attn_layers
    if kind == "train":
        tokens = seq * batch
        return 6.0 * n_eff * tokens + 3.0 * attn_per_tok_ctx * (seq / 2) * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n_eff * tokens + attn_per_tok_ctx * (seq / 2) * tokens
    # decode: one token per sequence against a seq-length cache
    return 2.0 * n_eff * batch + attn_per_tok_ctx * seq * batch


OPTS_HELP = (
    "comma-separated perf levers (DESIGN.md §9): bf16cast (bf16 param "
    "storage, f32 masters), bf16grads (bf16 grad accumulation), shardgrads "
    "(reduce-scatter grad carry), blockattn (flash-style blocked attention), "
    "chunk128 / ssd_bf16 (SSD shaping), remat_dots, micro_half / "
    "micro_quarter / micro_double (grad-accumulation depth), ep_data "
    "(token-moving expert parallelism), pod_fsdp (ZeRO across pods)")


def _apply_cfg_opts(cfg, opts: set):
    import dataclasses
    if "bf16cast" in opts:
        # store params in bf16 (AdamW keeps f32 masters in its state), so
        # ZeRO all-gathers move half the bytes. XLA reorders an explicit
        # pre-scan convert past the gather (measured: no effect), so the
        # storage dtype is the reliable lever.
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    if "blockattn" in opts:
        cfg = dataclasses.replace(cfg, attn_impl="blocked")
    if "chunk128" in opts and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=128))
    if "remat_dots" in opts:
        cfg = dataclasses.replace(cfg, remat="dots")
    if "ssd_bf16" in opts and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, intra_dtype="bfloat16"))
    return cfg


def _cell(arch: str, shape_name: str, multi_pod: bool, verbose=True,
          hlo_out=None, opts: set = frozenset()):
    cfg = _apply_cfg_opts(get_config(arch), opts)
    seq, batch, kind = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(cfg, mesh, opts=frozenset(opts))
    model = model_lib.build(cfg)
    n_dev = int(np.prod(mesh.devices.shape))

    params_meta = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_specs = plan.param_specs(params_meta)
    params_struct = values_of(params_meta)
    sh = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    repl = NamedSharding(mesh, PartitionSpec())

    t0 = time.time()
    with plan_context(plan):
        if kind == "train":
            optimizer = make_optimizer(cfg)
            opt_struct = jax.eval_shape(optimizer.init, params_struct)
            if cfg.optimizer == "adafactor":
                opt_specs = optimizer.state_spec_tree(param_specs, params_struct)
            else:
                opt_specs = optimizer.state_spec_tree(param_specs)
            batch_div = plan._batch_div()
            n_micro = n_microbatches(arch, batch, batch_div)
            if "micro_half" in opts:
                n_micro = max(n_micro // 2, 1)
            if "micro_quarter" in opts:
                n_micro = max(n_micro // 4, 1)
            if "micro_double" in opts:
                n_micro = min(n_micro * 2, batch // batch_div)
            _m = {"bf16_params": "bf16cast", "shard_grads": "shardgrads",
                  "bf16_grads": "bf16grads"}
            step_opts = frozenset(o for o, flag in _m.items() if flag in opts)
            step_fn = make_train_step(model, optimizer, n_micro,
                                      opts=step_opts,
                                      grad_specs=param_specs, mesh=mesh)
            bstruct = batch_struct(cfg, batch, seq, "train")
            bspecs = plan.batch_spec(bstruct)
            state_struct = TrainState(params=params_struct,
                                      opt_state=opt_struct,
                                      step=jax.ShapeDtypeStruct((), jnp.int32))
            state_specs = TrainState(params=param_specs, opt_state=opt_specs,
                                     step=PartitionSpec())
            mask_struct = jax.ShapeDtypeStruct((n_micro,), jnp.float32)
            jitted = jax.jit(
                step_fn,
                in_shardings=(sh(state_specs), sh(bspecs), repl),
                out_shardings=(sh(state_specs), repl))
            lowered = jitted.lower(state_struct, bstruct, mask_struct)
        elif kind == "prefill":
            bstruct = batch_struct(cfg, batch, seq, "prefill")
            bspecs = plan.batch_spec(bstruct)
            cache_struct = model.cache_spec(batch, seq)
            cache_specs = plan.cache_spec_tree(cache_struct, batch)
            logits_spec = PartitionSpec(plan.batch_axes, None, "model")
            jitted = jax.jit(
                lambda p, b: model.prefill(p, b),
                in_shardings=(sh(param_specs), sh(bspecs)),
                out_shardings=(NamedSharding(mesh, logits_spec),
                               sh(cache_specs)))
            lowered = jitted.lower(params_struct, bstruct)
        else:  # decode
            tok_struct = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
            cache_struct = model.cache_spec(batch, seq)
            cache_specs = plan.cache_spec_tree(cache_struct, batch)
            batch_ok = batch % plan._batch_div() == 0
            tok_spec = PartitionSpec(plan.batch_axes if batch_ok else None, None)
            logits_spec = PartitionSpec(plan.batch_axes if batch_ok else None,
                                        None, "model")
            jitted = jax.jit(
                model.decode_step,
                in_shardings=(sh(param_specs), NamedSharding(mesh, tok_spec),
                              sh(cache_specs)),
                out_shardings=(NamedSharding(mesh, logits_spec),
                               sh(cache_specs)))
            lowered = jitted.lower(params_struct, tok_struct, cache_struct)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    if hlo_out is not None:
        import gzip
        with gzip.open(hlo_out, "wt") as f:
            f.write(hlo)
    hla = analyze(hlo)  # trip-count-corrected flops / bytes / collectives
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(params_struct))
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev, "kind": kind, "seq": seq, "batch": batch,
        "n_params": n_params,
        # per-device, trip-count corrected (see hlo_analyzer.py)
        "flops_per_device": hla["dot_flops"],
        "hbm_bytes_per_device": hla["hbm_bytes"],
        "collective_wire_bytes": hla["wire_bytes"],
        "collectives": hla["collectives"],
        # raw XLA numbers (loop bodies counted once) for reference
        "xla_flops_raw": float(ca.get("flops", 0.0)),
        "xla_bytes_raw": float(ca.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "model_flops": model_flops_analytic(cfg, shape_name),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_bytes": len(hlo),
        "plan_notes": plan.notes,
    }
    if kind == "train":
        rec["n_micro"] = n_micro
    if verbose:
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB  "
              f"flops/dev={rec['flops_per_device']:.3e}  "
              f"wire={rec['collective_wire_bytes']/2**20:.1f}MiB  "
              f"compile={t_compile:.1f}s")
    return rec


def run_cell(arch, shape_name, mesh_kind, out_dir: Path, force=False,
             tag_suffix="", opts: set = frozenset()):
    tag = f"{arch}__{shape_name}__{mesh_kind}{tag_suffix}"
    path = out_dir / f"{tag}.json"
    if path.exists() and not force:
        print(f"[skip-cached] {tag}")
        return True
    ok, reason = shape_applicable(arch, shape_name)
    if not ok:
        path.write_text(json.dumps({
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "skipped": True, "reason": reason}, indent=1))
        print(f"[skip] {tag}: {reason}")
        return True
    print(f"[cell] {tag}")
    try:
        rec = _cell(arch, shape_name, multi_pod=(mesh_kind == "multi"),
                    hlo_out=out_dir / f"{tag}.hlo.gz", opts=opts)
        rec["opts"] = sorted(opts)
        path.write_text(json.dumps(rec, indent=1))
        return True
    except Exception as e:
        err = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "error": repr(e), "traceback": traceback.format_exc()}
        (out_dir / f"{tag}.FAILED.json").write_text(json.dumps(err, indent=1))
        print(f"[FAIL] {tag}: {e!r}")
        return False


def reanalyze(out_dir: Path):
    """Recompute analyzer-derived fields from stored HLO (no recompile)."""
    import gzip
    for hp in sorted(out_dir.glob("*.hlo.gz")):
        jp = out_dir / (hp.name[: -len(".hlo.gz")] + ".json")
        if not jp.exists():
            continue
        rec = json.loads(jp.read_text())
        hla = analyze(gzip.open(hp, "rt").read())
        rec["flops_per_device"] = hla["dot_flops"]
        rec["hbm_bytes_per_device"] = hla["hbm_bytes"]
        rec["collective_wire_bytes"] = hla["wire_bytes"]
        rec["collectives"] = hla["collectives"]
        jp.write_text(json.dumps(rec, indent=1))
        print(f"[reanalyzed] {jp.name}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute analyzer fields from stored HLO")
    ap.add_argument("--opt", default="", help=OPTS_HELP)
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    if args.reanalyze:
        reanalyze(out_dir)
        raise SystemExit(0)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    opts = set(o for o in args.opt.split(",") if o)
    suffix = ("__opt-" + "-".join(sorted(opts))) if opts else ""
    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                if not run_cell(arch, shape_name, mesh_kind, out_dir,
                                force=args.force, tag_suffix=suffix,
                                opts=opts):
                    failures += 1
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
