"""Multi-pod dry-run: prove the distribution config lowers + compiles for
every (architecture x input-shape x mesh) combination, and extract the
roofline terms from the compiled artifact.

MUST set the fake-device flag before ANY other import (jax locks the device
count on first init).
"""
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro import sharding as sh                     # noqa: E402
from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, draft_for,  # noqa: E402
                           get_config, supports_shape)
from repro.launch import hlocost                     # noqa: E402
from repro.launch.mesh import make_production_mesh   # noqa: E402
from repro.models import model as M                  # noqa: E402
from repro.optim import adamw                        # noqa: E402
from repro.serve import engine as E                  # noqa: E402
from repro.train import loop as TL                   # noqa: E402

NS = jax.sharding.NamedSharding

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# Per-arch training knobs for the dry-run (microbatching keeps the
# activation footprint inside HBM for the big configs — see EXPERIMENTS.md
# §Perf for the iteration that chose these).
TRAIN_MICROBATCH = {
    "nemotron-4-340b": 16,
    "deepseek-67b": 8,
    "kimi-k2-1t-a32b": 16,
    "llama-3.2-vision-11b": 4,
    "deepseek-7b": 2,
    "yi-6b": 2,
    "yi-6b-swa4k": 2,
}


# ---------------------------------------------------------------------------
# Public input_specs API (deliverable): ShapeDtypeStruct stand-ins for every
# model input of a given (arch, shape) case.
# ---------------------------------------------------------------------------


def input_specs(arch: str, shape_name: str, *, k_lookahead: int = 4
                ) -> Dict[str, Any]:
    """ShapeDtypeStructs for every input of the step that ``shape_name``
    lowers — no device allocation."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = M.abstract_batch(cfg, B, S)
        params = M.abstract_params(cfg, jnp.bfloat16)
        opt = opt_abstract(params)
        return {"params": params, "opt_state": opt, "batch": batch}
    if shape.kind == "prefill":
        batch = M.abstract_batch(cfg, B, S)
        params = M.abstract_params(cfg, jnp.bfloat16)
        return {"params": params, "batch": batch}
    # decode: speculative serve step (Alg. 1) against a seq_len cache
    dcfg = draft_for(cfg)
    scfg = E.SpecConfig(K=k_lookahead)
    params = M.abstract_params(cfg, jnp.bfloat16)
    d_params = M.abstract_params(dcfg, jnp.bfloat16)
    state = E.abstract_state(cfg, dcfg, scfg, B, S)
    return {"params": params, "d_params": d_params, "state": state}


def opt_abstract(params_abstract):
    """AdamW moments in f32 (master-precision), step counter i32."""
    f32 = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_abstract)
    return {"m": f32, "v": f32,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tok": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        nb = _DTYPE_BYTES.get(dt)
        if nb is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nb
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum operand sizes of every collective op in the (SPMD-partitioned)
    HLO.  Returns {'total': bytes, 'per_op': {op: {count, bytes}}}."""
    per_op: Dict[str, Dict[str, int]] = {}
    total = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([a-z\-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        if op.rstrip("-start").rstrip("-done") not in _COLLECTIVES \
                and op not in _COLLECTIVES:
            continue
        # operand types appear inside the call parens
        paren = s[s.index("(") + 1:]
        nb = _shape_bytes(paren)
        if nb == 0:  # fall back to result type
            nb = _shape_bytes(m.group(1))
        d = per_op.setdefault(op, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += nb
        total += nb
    return {"total": total, "per_op": per_op}


# ---------------------------------------------------------------------------
# Lowering per shape-kind
# ---------------------------------------------------------------------------


def apply_opt(cfg):
    """Beyond-paper optimized variant (see EXPERIMENTS.md §Perf):
    - chunked (SSD) scan for Mamba2-family recurrences (A);
    - explicit expert-buffer sharding constraints for MoE (B);
    - grouped-GQA decode attention with sequence-sharded scores (C)."""
    if cfg.ssm is not None:
        # mamba2: chunked SSD scan; rwkv6: VMEM-resident Pallas WKV kernel
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=128))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe_shard_constraints=True)
    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        cfg = dataclasses.replace(cfg, opt_decode=True)
    return cfg


def lower_case(arch: str, shape_name: str, mesh, *, k_lookahead: int = 4,
               microbatch: Optional[int] = None, opt: bool = False):
    """Returns (lowered, in_specs_for_report). Raises on sharding bugs."""
    cfg = get_config(arch)
    if opt:
        cfg = apply_opt(cfg)
    shape = INPUT_SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    specs = input_specs(arch, shape_name, k_lookahead=k_lookahead)

    if shape.kind == "train":
        p_spec = sh.param_specs(specs["params"], mesh)
        o_spec = sh.opt_state_specs(specs["params"], mesh)
        b_spec = sh.batch_spec(specs["batch"], mesh, global_batch=B)
        mb = microbatch or TRAIN_MICROBATCH.get(arch, 1)
        # a microbatch must still contain >=1 sequence per dp shard, or the
        # SPMD partitioner replicates the batch across the pod axis
        dp = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp *= mesh.shape[ax]
        mb = max(1, min(mb, B // dp))
        step = TL.make_train_step(
            cfg, adamw.AdamWConfig(), remat=True, microbatches=mb)
        jitted = jax.jit(
            step,
            in_shardings=(jax.tree.map(lambda s: NS(mesh, s), p_spec),
                          jax.tree.map(lambda s: NS(mesh, s), o_spec),
                          jax.tree.map(lambda s: NS(mesh, s), b_spec)),
            out_shardings=(jax.tree.map(lambda s: NS(mesh, s), p_spec),
                           jax.tree.map(lambda s: NS(mesh, s), o_spec),
                           None))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(specs["params"], specs["opt_state"],
                                   specs["batch"])
        return lowered

    if shape.kind == "prefill":
        p_spec = sh.param_specs(specs["params"], mesh)
        b_spec = sh.batch_spec(specs["batch"], mesh, global_batch=B)
        cache_abs = M.abstract_cache(cfg, B, S, jnp.bfloat16)
        c_spec = sh.cache_specs(cache_abs, mesh, global_batch=B)
        l_spec = sh.logits_spec(mesh, global_batch=B, vocab=cfg.vocab)

        def prefill_step(params, batch):
            return M.prefill(params, cfg, batch, S, cache_dtype=jnp.bfloat16)

        jitted = jax.jit(
            prefill_step,
            in_shardings=(jax.tree.map(lambda s: NS(mesh, s), p_spec),
                          jax.tree.map(lambda s: NS(mesh, s), b_spec)),
            out_shardings=(NS(mesh, l_spec),
                           jax.tree.map(lambda s: NS(mesh, s), c_spec)))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(specs["params"], specs["batch"])
        return lowered

    # ---- decode: the full sharded serve step (Alg. 1) ----
    # Routed through the engine's own mesh-aware jit builder, so the
    # dry-run lowers the exact program `generate(mesh=...)` serves with:
    # state + StepOutput batch-sharded via sharding.engine_state_specs,
    # the fused verify tail shard_mapped onto the per-shard local batch.
    dcfg = draft_for(cfg)
    if opt:
        dcfg = apply_opt(dcfg)
    scfg = E.SpecConfig(K=k_lookahead)
    p_spec = sh.param_specs(specs["params"], mesh)
    dp_spec = sh.param_specs(specs["d_params"], mesh)
    jitted = E.jitted_spec_step(
        cfg, dcfg, scfg, mesh, state_abs=specs["state"],
        t_shardings=jax.tree.map(lambda s: NS(mesh, s), p_spec),
        d_shardings=jax.tree.map(lambda s: NS(mesh, s), dp_spec))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(specs["params"], specs["d_params"],
                               specs["state"])
    return lowered


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_case(arch: str, shape_name: str, *, multi_pod: bool = False,
             save: bool = True, compile_: bool = True,
             microbatch: Optional[int] = None, opt: bool = False
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "variant": "opt" if opt else "baseline",
    }
    if not supports_shape(cfg, shape_name):
        rec["status"] = "SKIP(quadratic-attention)"
        _save(rec, save, opt)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        lowered = lower_case(arch, shape_name, mesh, microbatch=microbatch,
                             opt=opt)
        rec["lower_s"] = round(time.time() - t0, 1)
        if compile_:
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):  # older jax: one dict per dev
                ca = ca[0] if ca else {}
            # raw XLA numbers (while bodies counted once — see hlocost)
            rec["xla_flops_unscaled"] = float(ca.get("flops", -1))
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "generated_code_bytes":
                    getattr(ma, "generated_code_size_in_bytes", None),
            }
            # loop-scaled per-partition cost from the HLO structure
            hlo_text = compiled.as_text()
            cost = hlocost.module_cost(hlo_text)
            rec["flops"] = cost.flops            # per partition
            rec["hbm_bytes"] = cost.bytes        # per partition
            rec["collectives"] = {"total": cost.collective_bytes,
                                  "per_op": cost.per_collective}
            rec["bytes_by_op_top"] = dict(cost.top_bytes(8))
            _save_hlo(rec, hlo_text, opt)
        else:
            cost = hlocost.module_cost(lowered.as_text())
            rec["collectives"] = {"total": cost.collective_bytes,
                                  "per_op": cost.per_collective}
        rec["status"] = "OK"
    except Exception as e:  # noqa: BLE001 — report the failure, don't hide it
        rec["status"] = f"FAIL: {type(e).__name__}: {e}"[:500]
        rec["lower_s"] = round(time.time() - t0, 1)
    _save(rec, save, opt)
    return rec


def _save_hlo(rec: Dict[str, Any], text: str, opt: bool = False):
    """Gzip the compiled HLO so the roofline can be recomputed under an
    updated cost model without re-compiling."""
    import gzip
    d = os.path.join(ARTIFACT_DIR + ("_opt" if opt else ""), "hlo")
    os.makedirs(d, exist_ok=True)
    fn = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.hlo.gz"
    with gzip.open(os.path.join(d, fn), "wt") as f:
        f.write(text)


def _save(rec: Dict[str, Any], save: bool, opt: bool = False):
    if not save:
        return
    d = ARTIFACT_DIR + ("_opt" if opt else "")
    os.makedirs(d, exist_ok=True)
    fn = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    with open(os.path.join(d, fn), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-compile", action="store_true",
                    help="lower only (faster; no cost analysis)")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized variant (artifacts go to "
                    "dryrun_opt/)")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_case(arch, shape, multi_pod=mp,
                               compile_=not args.no_compile, opt=args.opt)
                flops = rec.get("flops")
                print(f"{arch:24s} {shape:12s} {rec['mesh']:8s} "
                      f"{rec['status']:30s} "
                      f"flops={flops:.3e}" if flops else
                      f"{arch:24s} {shape:12s} {rec['mesh']:8s} "
                      f"{rec['status']}",
                      flush=True)


if __name__ == "__main__":
    main()
