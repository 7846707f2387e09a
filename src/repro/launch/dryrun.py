"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: every cell's step function must ``.lower().compile()`` against the
production meshes — single-pod (16, 16) = 256 chips and multi-pod
(2, 16, 16) = 512 chips — with the per-cell sharding plan from specs.py.
The compiled artifact yields:

  * ``memory_analysis()``  — per-device bytes (args/temps/output): fits-check
  * ``cost_analysis()``    — XLA's flops/bytes (scan bodies counted once!)
  * HLO text               — trip-count-corrected FLOPs + collective bytes
                             via hlo_analysis.py (the roofline inputs)

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json;
EXPERIMENTS.md §Dry-run and §Roofline are generated from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod --precision w8a8
"""
from __future__ import annotations

# The CPU platform pin and the placeholder-device flag MUST precede any
# other import (including ``from repro...``): jax locks the platform and
# device count on first init.  Only the dry-run sets this — smoke tests and
# benches see 1 device, and on a TPU host the dry-run never opens the chip.
import os  # noqa: E402
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..configs import SHAPES, ARCH_IDS, cells, get_config
from ..dist.sharding import param_specs, set_axis_env
from ..models import ArchConfig, encode
from ..models.lm import forward, lm_loss
from ..quant import ptq_quantize_params
from ..serve.engine import decode_step, prefill_step
from ..train.optimizer import AdamWConfig, adamw_update, init_opt_state
from . import hlo_analysis
from .mesh import make_production_mesh
from .specs import (
    abstract_params,
    input_shardings,
    input_specs,
    make_cell_plan,
)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")


# ---------------------------------------------------------------------------
# step functions per cell kind
# ---------------------------------------------------------------------------

def _half(p):
    """Cast f32 master weights to bf16 BEFORE the FSDP all-gather: the
    gather (fwd + remat + bwd = 3 passes over every parameter) moves half
    the bytes; masters/optimizer stay f32 (the cast transpose returns f32
    grads)."""
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if (hasattr(x, "dtype") and x.dtype == jnp.float32 and x.ndim >= 2)
        else x, p)


def _train_step(cfg: ArchConfig, params, opt_state, batch):
    def loss_fn(p):
        ph = _half(p)
        if cfg.is_encoder_decoder:
            from ..models import encdec_loss
            return encdec_loss(ph, cfg, batch["frames"], batch["tokens"],
                               batch["labels"])
        return lm_loss(ph, cfg, batch["tokens"], batch["labels"],
                       kv_source=batch.get("kv_source"),
                       embeddings=None)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, opt_state, metrics = adamw_update(
        AdamWConfig(), params, grads, opt_state)
    return params, opt_state, loss


def _serve_step(cfg: ArchConfig, kind: str, params, tokens, positions, states,
                kv_source=None):
    p = params["decoder"] if cfg.is_encoder_decoder else params
    if kind == "prefill":
        return prefill_step(p, cfg, tokens, positions, states,
                            kv_source=kv_source)
    return decode_step(p, cfg, tokens, positions, states, kv_source=kv_source)


# ---------------------------------------------------------------------------
# multi-stage pipeline dry run
# ---------------------------------------------------------------------------

def run_pipeline_cell(n_stages: int = 4, n_microbatches: int = 8,
                      n_layers: int = 8, d_model: int = 512,
                      microbatch: int = 4, save: bool = True) -> dict:
    """Compile the GPipe schedule on a REAL multi-stage placeholder mesh.

    ``dist.pipeline.pipeline_apply`` was previously only exercised on one
    stage (tests/test_pipeline.py), where the ppermute rotation and the
    last-stage psum-broadcast are degenerate.  This cell runs it under
    ``shard_map`` over an ``n_stages``-way "stage" axis: each stage owns a
    contiguous layer slab (the stage axis shards the stacked layer dim —
    the shard_map form of ``split_stages``), activations rotate via
    collective-permute every schedule step, and the compiled HLO must show
    the M + S - 1 step structure.
    """
    assert n_stages >= 2, "the point is a MULTI-stage schedule"
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    from jax.sharding import PartitionSpec as P

    from ..dist.pipeline import bubble_fraction, pipeline_apply

    mesh = jax.make_mesh((n_stages,), ("stage",))

    def layer_fn(w, h):
        return jnp.tanh(h @ w)

    def run(stage_params, xs):
        return pipeline_apply(layer_fn, stage_params, xs, axis_name="stage")

    fn = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P("stage"), P()), out_specs=P(),
        check_vma=False))
    layers_abs = jax.ShapeDtypeStruct((n_layers, d_model, d_model),
                                      jnp.float32)
    xs_abs = jax.ShapeDtypeStruct((n_microbatches, microbatch, d_model),
                                  jnp.float32)
    t0 = time.time()
    with mesh:
        lowered = fn.lower(layers_abs, xs_abs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    hlo = hlo_analysis.analyze(compiled.as_text())
    n_steps = n_microbatches + n_stages - 1
    record = {
        "kind": "pipeline", "n_stages": n_stages,
        "n_microbatches": n_microbatches, "n_layers": n_layers,
        "d_model": d_model, "microbatch": microbatch,
        "schedule_steps": n_steps,
        "bubble_fraction": round(bubble_fraction(n_stages, n_microbatches), 4),
        "hlo": {
            "flops_per_device": hlo.flops,
            "collective_bytes_per_device": hlo.coll_bytes,
            "collective_counts": {k: float(v)
                                  for k, v in hlo.coll_counts.items()},
        },
        "timing": {"lower_s": round(t_lower, 2),
                   "compile_s": round(t_compile, 2)},
    }
    # the schedule's signature: one activation rotation per step (ppermute
    # may lower as -start/-done pairs or be trip-counted inside the while)
    assert record["hlo"]["collective_counts"].get("collective-permute", 0) \
        >= n_steps, record["hlo"]["collective_counts"]
    if save:
        sub = os.path.join(RESULTS_DIR, "pipeline")
        os.makedirs(sub, exist_ok=True)
        name = f"stage{n_stages}__mb{n_microbatches}.json"
        with open(os.path.join(sub, name), "w") as f:
            json.dump(record, f, indent=1)
    return record


# ---------------------------------------------------------------------------
# serving-TP dry run
# ---------------------------------------------------------------------------

def run_tp_serve_cell(overlap: str, tp: int = 8, save: bool = True) -> dict:
    """Compile the tp-sharded packed serving step and assert its
    collective STRUCTURE from the HLO.

    Serving TP's bit-identity contract (dist/tp.py) rests on the sharded
    program containing ONLY data-movement collectives — no all-reduce and
    no reduce-scatter anywhere (either would sum partial f32 products in
    a shard-count-dependent order).  On top of that, each boundary
    variant has a signature: barrier programs rebuild rows with
    all-gather only; overlap programs carry the all-to-all token split
    plus the sequence-parallel row gathers.  This cell is the compile-
    time proof — scripts/tp_equiv_smoke.py is the runtime one.
    """
    import dataclasses

    from ..models import init_params
    from ..serve import ServeConfig, ServingEngine

    cfg = dataclasses.replace(get_config("codeqwen1.5-7b", reduced=True),
                              n_heads=8, n_kv_heads=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg,
                        ServeConfig(batch_lanes=2, max_seq=64,
                                    token_budget=8, tp=tp,
                                    tp_overlap=overlap))
    b = eng.scfg.batch_lanes
    t = eng._buckets[-1] if eng._buckets else 1
    t0 = time.time()
    lowered = eng._step_fn.lower(
        eng.params, jnp.zeros((b, t), jnp.int32),
        jnp.full((b, t), -1, jnp.int32), eng.states,
        jnp.ones((b,), bool), jnp.zeros((b,), jnp.int32), True, 1)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    hlo = hlo_analysis.analyze(compiled.as_text())
    cc = {k: float(v) for k, v in hlo.coll_counts.items()}
    # exactness invariant: data movement only, never cross-shard sums
    assert not cc.get("all-reduce") and not cc.get("reduce-scatter"), \
        f"serving TP compiled a reducing collective: {cc}"
    if overlap == "barrier":
        assert cc.get("all-gather", 0) >= 1, cc
        assert not cc.get("all-to-all"), \
            f"barrier variant must not all-to-all: {cc}"
    else:
        assert cc.get("all-to-all", 0) >= 1, \
            f"overlap variant lost its token-split all-to-all: {cc}"
        assert cc.get("all-gather", 0) >= 1, cc
    record = {
        "kind": "tp_serve", "tp": tp, "overlap": overlap,
        "batch_lanes": b, "bucket": t,
        "hlo": {
            "flops_per_device": hlo.flops,
            "collective_bytes_per_device": hlo.coll_bytes,
            "collective_counts": cc,
        },
        "timing": {"lower_s": round(t_lower, 2),
                   "compile_s": round(t_compile, 2)},
    }
    if save:
        sub = os.path.join(RESULTS_DIR, "tp_serve")
        os.makedirs(sub, exist_ok=True)
        with open(os.path.join(sub, f"serve_tp{tp}_{overlap}.json"),
                  "w") as f:
            json.dump(record, f, indent=1)
    return record


# ---------------------------------------------------------------------------
# single-cell dry run
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             precision: str = "bf16", int8_kv: bool = False,
             fsdp: bool = True, save: bool = True,
             variant: str = "baseline") -> dict:
    cfg = get_config(arch, precision=precision)
    shape = SHAPES[shape_name]
    kind = shape["kind"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_cell_plan(cfg, mesh, kind, shape["global_batch"], fsdp=fsdp,
                          variant=variant)
    set_axis_env(plan.env)
    t0 = time.time()

    params_abs = abstract_params(cfg)
    if precision == "w8a8":
        params_abs = jax.eval_shape(ptq_quantize_params, params_abs)
    elif kind in ("prefill", "decode") and variant != "serve_f32":
        # serving reads weights every token: bf16 checkpoint cast at load
        # (masters stay f32 in the training job)
        params_abs = jax.tree.map(
            lambda x: (jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
                       if x.dtype == jnp.float32 and len(x.shape) >= 2 else x),
            params_abs)
    pspec = param_specs(params_abs)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
    specs = input_specs(cfg, kind, shape["seq_len"], shape["global_batch"],
                        int8_kv=int8_kv)
    ishard = input_shardings(cfg, kind, specs, plan, mesh)

    if kind == "train":
        opt_abs = jax.eval_shape(init_opt_state, params_abs)
        oshard = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            jax.tree.map(lambda _: None, opt_abs))  # placeholder
        # opt state shards like params (mu/nu mirror the param tree)
        from ..train.optimizer import OptState
        oshard = OptState(
            step=NamedSharding(mesh, jax.sharding.PartitionSpec()),
            mu=pshard, nu=jax.tree.map(lambda x: x, pshard))
        step = functools.partial(_train_step, cfg)
        args = (params_abs, opt_abs,
                {k: specs[k] for k in specs})
        in_shardings = (pshard, oshard, {k: ishard[k] for k in specs})
        fn = jax.jit(step, in_shardings=in_shardings,
                     donate_argnums=(0, 1))
    else:
        step = functools.partial(_serve_step, cfg, kind)
        args = (params_abs, specs["tokens"], specs["positions"],
                specs["states"])
        in_shardings = (pshard, ishard["tokens"], ishard["positions"],
                        ishard["states"])
        if "kv_source" in specs:
            args = args + (specs["kv_source"],)
            in_shardings = in_shardings + (ishard["kv_source"],)
        fn = jax.jit(step, in_shardings=in_shardings, donate_argnums=(3,))

    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    hlo = hlo_analysis.analyze(text)
    n_dev = mesh.size

    record = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "precision": precision, "int8_kv": int8_kv,
        "plan": {
            "batch_axes": list(plan.batch_axes),
            "kv_heads_on_model": plan.kv_heads_on_model,
            "ep_mode": plan.ep_mode,
            "seq_axes_kv": list(plan.seq_axes_kv),
            "fsdp": fsdp and kind == "train",
        },
        "memory": {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "output_bytes_per_device": int(ma.output_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "alias_bytes_per_device": int(ma.alias_size_in_bytes),
            "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                         + ma.output_size_in_bytes
                                         + ma.temp_size_in_bytes
                                         - ma.alias_size_in_bytes),
        },
        "cost_analysis_raw": {
            "flops_per_device_scan_uncorrected": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "hlo": {
            "flops_per_device": hlo.flops,
            "collective_bytes_per_device": hlo.coll_bytes,
            "mem_bytes_per_device": hlo.mem_bytes,
            "collective_counts": {k: float(v) for k, v in hlo.coll_counts.items()},
        },
        "timing": {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)},
    }
    if save:
        sub = os.path.join(RESULTS_DIR, record["mesh"])
        os.makedirs(sub, exist_ok=True)
        suffix = "" if precision == "bf16" else f"__{precision}"
        with open(os.path.join(sub, f"{arch}__{shape_name}{suffix}.json"), "w") as f:
            json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--precision", default="bf16", choices=["bf16", "w8a8"])
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="compile the multi-stage GPipe schedule cells "
                         "(2 and 4 stages) instead of the model cells")
    ap.add_argument("--tp-serve", action="store_true",
                    help="compile the tp=8 sharded packed serving step "
                         "(barrier + overlap) and assert the collective "
                         "structure: no all-reduce/reduce-scatter ever; "
                         "all-to-all only in the overlap variant")
    args = ap.parse_args()

    if args.tp_serve:
        n_fail = 0
        for overlap in ("barrier", "overlap"):
            tag = f"[tp-serve] tp=8 {overlap}"
            try:
                rec = run_tp_serve_cell(overlap)
                cc = rec["hlo"]["collective_counts"]
                print(f"OK   {tag}: ag={cc.get('all-gather', 0):.0f} "
                      f"a2a={cc.get('all-to-all', 0):.0f} "
                      f"ar={cc.get('all-reduce', 0):.0f} "
                      f"compile {rec['timing']['compile_s']}s", flush=True)
            except Exception as e:
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                n_fail += 1
        raise SystemExit(1 if n_fail else 0)

    if args.pipeline:
        n_fail = 0
        for n_stages, n_mb in ((2, 4), (4, 8)):
            tag = f"[pipeline] {n_stages} stages x {n_mb} microbatches"
            try:
                rec = run_pipeline_cell(n_stages=n_stages,
                                        n_microbatches=n_mb)
                cc = rec["hlo"]["collective_counts"]
                print(f"OK   {tag}: {rec['schedule_steps']} steps, "
                      f"bubble {rec['bubble_fraction']:.2f}, "
                      f"permutes {cc.get('collective-permute', 0):.0f}, "
                      f"compile {rec['timing']['compile_s']}s", flush=True)
            except Exception as e:
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                n_fail += 1
        raise SystemExit(1 if n_fail else 0)

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multi_pod_only:
        meshes = [True]

    n_ok = n_fail = 0
    for multi_pod in meshes:
        for arch in archs:
            shape_names = [args.shape] if args.shape else cells(arch)
            if args.precision == "w8a8":
                # W8A8 is the paper's INFERENCE mode: no gradients through
                # int8 weights — train cells stay bf16
                shape_names = [s for s in shape_names
                               if SHAPES[s]["kind"] != "train"]
            for shape_name in shape_names:
                tag = (f"[{'2x16x16' if multi_pod else '16x16'}] "
                       f"{arch} x {shape_name} ({args.precision})")
                try:
                    rec = run_cell(arch, shape_name, multi_pod,
                                   precision=args.precision,
                                   int8_kv=args.int8_kv)
                    mem = rec["memory"]["peak_bytes_per_device"] / 2 ** 30
                    fl = rec["hlo"]["flops_per_device"]
                    cb = rec["hlo"]["collective_bytes_per_device"] / 2 ** 20
                    print(f"OK   {tag}: peak {mem:.2f} GiB/dev, "
                          f"{fl:.3e} flops/dev, {cb:.1f} MiB coll/dev, "
                          f"compile {rec['timing']['compile_s']}s", flush=True)
                    n_ok += 1
                except Exception as e:
                    print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
                    n_fail += 1
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
