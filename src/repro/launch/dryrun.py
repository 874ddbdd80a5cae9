"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
      --shape train_4k [--multi-pod] [--no-calibrate] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per cell this produces:
  * proof of compile on the production mesh (16x16, and 2x16x16 multi-pod);
  * memory_analysis (bytes/device — proves it fits);
  * cost_analysis + trip-count calibration -> per-device HLO FLOPs/bytes;
  * collective census (op counts + operand bytes from optimized HLO);
  * the three roofline terms (launch/roofline.py).
Results land in experiments/dryrun/<arch>_<shape>_<mesh>.json.
"""
# The VERY FIRST lines — before ANY other import, jax locks the device
# count on first init. The dry run compiles for 512 fake host devices and
# never takes a chip:
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ARCHS, get_config, get_shape, shapes_for,
                           ALL_SHAPES)  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.launch import roofline as RL  # noqa: E402
from repro.launch import specs as S      # noqa: E402
from repro.launch.mesh import describe, make_production_mesh  # noqa: E402
from repro.models import model as M      # noqa: E402
from repro.models import sharding as shd  # noqa: E402
from repro.models.common import (abstract, bytes_per_device,  # noqa: E402
                                 shardings, shardings_inference)
from repro.optim import get_optimizer    # noqa: E402
from repro.train.steps import (make_decode_step, make_prefill_step,  # noqa
                               make_train_step)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _params_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def build_lowering(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Lower the cell's step function against ShapeDtypeStructs."""
    defs = M.model_def(cfg)
    p_abs = abstract(defs, _params_dtype(cfg))
    if shape.kind == "train":
        p_shd = shardings(defs, mesh)
    else:
        # inference: drop FSDP unless TP-only sharding cannot fit (12 GiB
        # param budget per v5e chip) — kills per-step param all-gathers
        keep_fsdp = bytes_per_device(defs, mesh, keep_fsdp=False) \
            > 12 * 2**30
        p_shd = shardings_inference(defs, mesh, keep_fsdp=keep_fsdp)

    with shd.use_mesh(mesh):
        if shape.kind == "train":
            opt = get_optimizer(cfg.optimizer, lr=1e-4)
            sdefs = opt.state_defs(defs)
            o_abs = abstract(sdefs)
            o_shd = shardings(sdefs, mesh)
            bspec = S.train_batch_specs(cfg, shape.global_batch,
                                        shape.seq_len)
            b_shd = S.batch_shardings(cfg, mesh, bspec)
            step = make_train_step(cfg, opt)
            jitted = jax.jit(step, in_shardings=(p_shd, o_shd, b_shd),
                             out_shardings=(p_shd, o_shd, None),
                             donate_argnums=(0, 1))
            return jitted.lower(p_abs, o_abs, bspec)

        if shape.kind == "prefill":
            bspec = S.train_batch_specs(cfg, shape.global_batch,
                                        shape.seq_len)
            bspec.pop("labels")
            b_shd = S.batch_shardings(cfg, mesh, bspec)
            step = make_prefill_step(cfg, s_max=shape.seq_len)
            jitted = jax.jit(step, in_shardings=(p_shd, b_shd))
            return jitted.lower(p_abs, bspec)

        # decode: one new token against a seq_len cache
        tokens, cache_abs, extras = S.decode_input_specs(cfg, shape)
        c_shd = S.cache_shardings(cfg, mesh, cache_abs, shape.global_batch)
        t_shd = S.batch_shardings(cfg, mesh, {"tokens": tokens})["tokens"]
        step = make_decode_step(cfg)
        index = shape.seq_len - 1
        if cfg.family == "vlm":
            pos3 = extras["positions3"]
            jitted = jax.jit(
                lambda p, t, c, q: step(p, t, c, index, positions3=q),
                in_shardings=(p_shd, t_shd, c_shd, None),
                donate_argnums=(2,))
            return jitted.lower(p_abs, tokens, cache_abs, pos3)
        jitted = jax.jit(lambda p, t, c: step(p, t, c, index),
                         in_shardings=(p_shd, t_shd, c_shd),
                         donate_argnums=(2,))
        return jitted.lower(p_abs, tokens, cache_abs)


def _cost_dict(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def _calibration_cfg(cfg: ModelConfig, repeats: int) -> ModelConfig:
    plen = len(cfg.block_pattern)
    over = dict(n_layers=plen * repeats, scan_unroll=True, grad_accum=1)
    if cfg.is_encdec:
        over["encoder_layers"] = repeats
    return dataclasses.replace(cfg, **over)


def calibrate_costs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """Extrapolate per-device FLOPs/bytes/collective-bytes to full depth:
    total = c1 + (R-1)·(c2-c1), with unrolled 1- and 2-repeat variants."""
    out = {}
    for r in (1, 2):
        ccfg = _calibration_cfg(cfg, r)
        lowered = build_lowering(ccfg, shape, mesh)
        compiled = lowered.compile()
        cd = _cost_dict(compiled)
        cs = RL.collective_stats(compiled.as_text())
        out[r] = {"flops": cd["flops"], "bytes": cd["bytes"],
                  "coll": float(cs["total_bytes"]),
                  "coll_counts": cs["counts"]}
    R = cfg.n_repeats
    extr = {}
    for key in ("flops", "bytes", "coll"):
        c1, c2 = out[1][key], out[2][key]
        extr[key] = c1 + (R - 1) * (c2 - c1)
    extr["per_repeat"] = {k: out[2][k] - out[1][k]
                          for k in ("flops", "bytes", "coll")}
    extr["calib_counts"] = out[2]["coll_counts"]
    # grad-accum: calibration ran accum=1 at full global batch == same
    # total tokens, so no further scaling is needed.
    n_slstm = sum(1 for b in cfg.layer_types() if b == "slstm")
    extr["flops"] += RL.slstm_flops_correction(cfg, shape, n_slstm) / \
        _mesh_chips(mesh)
    return extr


def _mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


def _parse_overrides(pairs: list[str] | None) -> dict:
    out = {}
    for kv in pairs or []:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        out[k] = v
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             calibrate: bool = True, out_dir: str = OUT_DIR,
             overrides: dict | None = None, tag: str = "") -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = _mesh_chips(mesh)
    mesh_name = ("multipod" if multi_pod else "pod") + (f"_{tag}" if tag else "")
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": describe(mesh),
                 "chips": n_chips, "status": "ok",
                 "overrides": overrides or {}}

    if shape_name not in [s.name for s in shapes_for(cfg)]:
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch skips long_500k"
                         if shape_name == "long_500k" else "n/a")
        _write(rec, arch, shape_name, mesh_name, out_dir)
        return rec

    t0 = time.time()
    lowered = build_lowering(cfg, shape, mesh)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_est_bytes": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
    }
    rec["cost_raw"] = _cost_dict(compiled)
    cs = RL.collective_stats(compiled.as_text())
    rec["collectives_raw"] = cs

    if calibrate:
        extr = calibrate_costs(cfg, shape, mesh)
        rec["cost_calibrated"] = {k: extr[k]
                                  for k in ("flops", "bytes", "coll")}
        rec["per_repeat"] = extr["per_repeat"]
        n_active = M.count_active_params(cfg)
        mf = RL.model_flops(cfg, shape, n_active)
        terms = RL.derive_terms(extr["flops"], extr["bytes"], extr["coll"],
                                mf, n_chips)
        rec["n_active_params"] = n_active
        rec["n_params"] = M.count_params(cfg)
        rec["roofline"] = terms.to_dict()
    _write(rec, arch, shape_name, mesh_name, out_dir)
    return rec


def _write(rec, arch, shape_name, mesh_name, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=[s.name for s in ALL_SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="config overrides for perf experiments, e.g. "
                         "--override remat_policy=dots --override "
                         "grad_accum=4")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json filename")
    args = ap.parse_args()
    overrides = _parse_overrides(args.override)

    cells = []
    if args.all:
        for a in ARCHS:
            for s in ALL_SHAPES:
                cells.append((a, s.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch/--shape required without --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
            try:
                t0 = time.time()
                rec = run_cell(arch, shape_name, mp,
                               calibrate=not args.no_calibrate,
                               out_dir=args.out, overrides=overrides,
                               tag=args.tag)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    peak = rec["memory"]["peak_est_bytes"] / 2**30
                    extra = (f" compile={rec['compile_s']}s "
                             f"peak/dev={peak:.2f}GiB")
                    if "roofline" in rec:
                        extra += (" bottleneck="
                                  f"{rec['roofline']['bottleneck']}")
                print(f"[{time.time()-t0:7.1f}s] {tag}: {status}{extra}",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"[ FAIL ] {tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
