"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input shape × mesh) cell from
ShapeDtypeStructs — no allocation — and records memory_analysis(),
cost_analysis() and the collective schedule for the roofline analysis.

``main()`` starts by forcing 512 host devices via ``XLA_FLAGS`` — that
must happen before jax initializes a backend (jax locks the device count
at first init), which holds for the CLI entry because importing jax does
not initialize one. It must NOT happen at module import: this module is a
library too (``lower_cell`` is imported by the tests), and an
importing process — smoke tests, benches, the service — must keep seeing
1 device. ``parity-lint``'s ``ordering-import-env-mutation`` rule
enforces the distinction repo-wide.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out experiments/dryrun
"""
import argparse
import os
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SHAPES, ArchConfig, ShapeConfig, cell_supported
from ..distribution.annotate import annotation_mesh
from ..distribution.sharding import (batch_shardings, cache_shardings,
                                     mesh_axes, param_shardings, _pick)
from ..models.transformer import decode_step, init_cache, init_params, prefill
from ..roofline.analysis import (analytic_cost, model_flops,
                                 parse_collectives, roofline)
from ..training.optimizer import OptimizerConfig
from ..training.train_step import TrainConfig, init_train_state, make_train_step
from .mesh import make_production_mesh


# ------------------------------------------------------------- input specs
def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s + 1), i32)}
        if cfg.family == "vlm":
            batch["positions"] = jax.ShapeDtypeStruct((b, s + 1, 3), i32)
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), f32)
        if cfg.family == "audio":
            batch["audio_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_audio_frames, cfg.d_model), f32)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.family == "vlm":
            batch["positions"] = jax.ShapeDtypeStruct((b, s, 3), i32)
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), f32)
        if cfg.family == "audio":
            batch["audio_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_audio_frames, cfg.d_model), f32)
        return batch
    # decode: one new token against a seq_len-deep cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
            "cache_len": jax.ShapeDtypeStruct((), i32)}


def _logits_sharding(mesh, cfg: ArchConfig, batch: int):
    dp, tp = mesh_axes(mesh)
    return NamedSharding(mesh, P(_pick(mesh, batch, [dp]),
                                 _pick(mesh, cfg.vocab, [tp])))


# ------------------------------------------------------------------- cells
def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
               microbatches: int = 1, remat: str = "full",
               layout: str = "2d"):
    """Returns the lowered computation. Raises on sharding/lowering errors."""
    with annotation_mesh(mesh, layout):
        return _lower_cell_inner(cfg, shape, mesh, microbatches=microbatches,
                                 remat=remat, layout=layout)


def _lower_cell_inner(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
                      microbatches: int, remat: str, layout: str):
    specs = input_specs(cfg, shape)
    params_t = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    p_sh = param_shardings(mesh, params_t)

    if shape.kind == "train":
        opt_cfg = OptimizerConfig()
        tc = TrainConfig(microbatches=microbatches, remat=remat)
        state_t = jax.eval_shape(
            lambda: init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0)))
        state_sh = {"params": p_sh,
                    "opt": {"mu": p_sh, "nu": p_sh,
                            "step": NamedSharding(mesh, P())}}
        b_sh = batch_shardings(mesh, specs, layout)
        metrics_sh = {"loss": NamedSharding(mesh, P()),
                      "grad_norm": NamedSharding(mesh, P()),
                      "lr": NamedSharding(mesh, P())}
        fn = make_train_step(cfg, opt_cfg, tc)
        lowered = jax.jit(fn, in_shardings=(state_sh, b_sh),
                          out_shardings=(state_sh, metrics_sh),
                          donate_argnums=0).lower(state_t, specs)
        return lowered

    if shape.kind == "prefill":
        b_sh = batch_shardings(mesh, specs, layout)
        cache_t = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        c_sh = cache_shardings(mesh, cache_t, shape.global_batch, layout)

        def fn(params, batch):
            return prefill(cfg, params, batch, max_len=shape.seq_len)

        out_sh = (_logits_sharding(mesh, cfg, shape.global_batch), c_sh,
                  NamedSharding(mesh, P()))
        lowered = jax.jit(fn, in_shardings=(p_sh, b_sh),
                          out_shardings=out_sh).lower(params_t, specs)
        return lowered

    # decode
    cache_t = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
    c_sh = cache_shardings(mesh, cache_t, shape.global_batch, layout)
    specs_d = input_specs(cfg, shape)
    tok_sh = NamedSharding(
        mesh, P(_pick(mesh, shape.global_batch,
                      [mesh_axes(mesh, layout)[0]]), None))

    def fn(params, cache, tokens, cache_len):
        return decode_step(cfg, params, cache, tokens, cache_len)

    out_sh = (_logits_sharding(mesh, cfg, shape.global_batch), c_sh)
    lowered = jax.jit(
        fn, in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P())),
        out_shardings=out_sh, donate_argnums=1,
    ).lower(params_t, cache_t, specs_d["tokens"], specs_d["cache_len"])
    return lowered


def run_cell(arch_name: str, shape_name: str, mesh_kind: str, *,
             microbatches: int = 1, remat: str = "full", layout: str = "2d",
             collect_hlo: bool = True) -> dict:
    cfg = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
                 "microbatches": microbatches, "remat": remat,
                 "layout": layout}
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    try:
        t0 = time.perf_counter()
        lowered = lower_cell(cfg, shape, mesh, microbatches=microbatches,
                             remat=remat, layout=layout)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        coll = parse_collectives(compiled.as_text(), n_chips) if collect_hlo \
            else None
        mf = model_flops(cfg, shape)
        # cost_analysis counts scan bodies once; the roofline terms use the
        # analytic (trip-count-exact) cost, validated in tests/test_roofline
        mb_used = microbatches if shape.kind == "train" else 1
        a_flops, a_bytes = analytic_cost(cfg, shape, remat, n_chips)
        rl = roofline(a_flops, a_bytes,
                      coll.total_wire_bytes if coll else 0.0, n_chips, mf)
        rec.update(
            status="ok", n_chips=n_chips,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes_per_chip": mem.argument_size_in_bytes,
                "output_bytes_per_chip": mem.output_size_in_bytes,
                "temp_bytes_per_chip": mem.temp_size_in_bytes,
                "alias_bytes_per_chip": mem.alias_size_in_bytes,
                "peak_bytes_per_chip": (mem.argument_size_in_bytes
                                        + mem.output_size_in_bytes
                                        + mem.temp_size_in_bytes
                                        - mem.alias_size_in_bytes),
            },
            cost={"hlo_flops_per_chip": flops,
                  "hlo_bytes_per_chip": bytes_acc,
                  "analytic_flops_per_chip": a_flops,
                  "analytic_bytes_per_chip": a_bytes},
            collectives=coll.to_json() if coll else None,
            roofline=rl.to_json(),
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def force_host_devices(n: int = 512) -> None:
    """Point XLA at ``n`` host platform devices — CLI entry points only,
    and only before jax's first backend init."""
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"


def main() -> None:
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--layout", default="2d", choices=["2d", "dp", "2d_seq"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_err = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, mesh_kind,
                               microbatches=args.microbatches,
                               remat=args.remat, layout=args.layout)
                path = os.path.join(args.out,
                                    f"{arch}__{shape}__{mesh_kind}.json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                dt = time.perf_counter() - t0
                if rec["status"] == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"[ok]   {arch:22s} {shape:12s} {mesh_kind:6s} "
                          f"compile={rec['compile_s']:7.1f}s "
                          f"peakmem={rec['memory']['peak_bytes_per_chip']/2**30:6.2f}GiB "
                          f"dom={r['dominant']:10s} "
                          f"useful={r['useful_ratio']:6.3f} ({dt:.0f}s)",
                          flush=True)
                elif rec["status"] == "skipped":
                    n_skip += 1
                    print(f"[skip] {arch:22s} {shape:12s} {mesh_kind:6s} "
                          f"{rec['reason']}", flush=True)
                else:
                    n_err += 1
                    print(f"[ERR]  {arch:22s} {shape:12s} {mesh_kind:6s} "
                          f"{rec['error']}", flush=True)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
