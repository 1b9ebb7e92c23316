import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("DRYRUN_DEVICES", "512")).strip()
# ^ must precede every other import: jax locks the device count on first init.
"""Multi-pod dry-run: AOT-lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the real train_step / prefill / decode_step under
the production mesh with the production shardings, compiles it, and records
memory_analysis / cost_analysis / collective mix — proving the distribution
config is coherent without hardware.  Results append incrementally to a JSON
file consumed by EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
"""
import argparse
import functools
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_arch
from ..models import Model
from ..optim import adamw
from ..sharding import rules as shr
from ..train.train_step import TrainConfig, make_train_step
from . import roofline as rl
from .mesh import make_production_mesh
from .shapes import SHAPES, ShapeCase, batch_specs, cell_supported


# ---------------------------------------------------------------------------
# Sharding of abstract inputs
# ---------------------------------------------------------------------------

def _is_logical(x) -> bool:
    return isinstance(x, tuple) and (len(x) == 0 or
                                     isinstance(x[0], (str, type(None))))


def param_shardings(model: Model, mesh, rules=None):
    return jax.tree.map(
        lambda lg, sh: shr.named_sharding(mesh, lg, sh.shape, rules),
        model.logical_axes(), model.param_shapes(), is_leaf=_is_logical)


def state_struct(model: Model):
    shapes = model.param_shapes()
    return {"params": shapes,
            "opt": {"m": shapes, "v": shapes,
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}}


def state_shardings(model: Model, mesh, rules=None, opt_rules=None):
    """params under ``rules``; optimizer moments optionally under different
    rules (ZeRO-1: params TP-replicated for compute, moments fully sharded)."""
    p = param_shardings(model, mesh, rules)
    o = param_shardings(model, mesh, opt_rules) if opt_rules is not None \
        else p
    return {"params": p,
            "opt": {"m": o, "v": o, "step": NamedSharding(mesh, P())}}


def serve_param_struct(model: Model):
    """Serving params are bf16 (weight-only cast, standard deployment)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        model.param_shapes())


def serve_rules(model: Model, mesh):
    """TP serving; weight-gathered (ZeRO-inference) only when bf16 weights
    exceed the per-device HBM budget under pure TP (e.g. qwen3-235b)."""
    tp = mesh.shape.get("model", 1)
    bytes_tp = model.param_count() * 2 / tp
    if bytes_tp > 12 * 2 ** 30:
        return shr.FSDP_RULES
    return None


def batch_shardings(batch_struct: Dict, mesh):
    out = {}
    for k, v in batch_struct.items():
        b = v.shape[0]
        lead = shr.batch_sharding(mesh, b)
        spec = lead.spec
        out[k] = NamedSharding(mesh, P(*(list(spec) + [None] *
                                         (len(v.shape) - len(spec)))))
    return out


_CACHE_LOGICAL = {
    # leaf name -> logical axes, rightmost dims (leading dims -> None).
    # Dense caches shard their depth (kv_seq) over 'model': every assigned
    # arch has kv_heads <= 8, which never divides a 16-way model axis.
    "k": ("batch", "kv_seq", None, None),
    "v": ("batch", "kv_seq", None, None),
    "xk": ("batch", "kv_seq", None, None),
    "xv": ("batch", "kv_seq", None, None),
    "kpos": (None,),
    "h": ("batch", "rnn"),
    "conv": ("batch", None, "rnn"),
    "s": ("batch", "heads", None, None),
    "shift_t": ("batch", None),
    "shift_c": ("batch", None),
}


def cache_shardings(cache_struct, mesh):
    def leaf(path, s):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = str(p.key)
                break
        logical = _CACHE_LOGICAL[name]
        full = (None,) * (len(s.shape) - len(logical)) + logical
        # batch axis respects divisibility (B=1 long_500k -> replicated)
        spec = []
        for dim, lg in zip(s.shape, full):
            if lg == "batch":
                spec.append(shr.batch_sharding(mesh, dim).spec[0]
                            if shr.batch_sharding(mesh, dim).spec else None)
            elif lg is None:
                spec.append(None)
            else:
                ps = shr.partition_spec((lg,), (dim,), mesh)
                spec.append(ps[0])
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map_with_path(leaf, cache_struct)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def build_lowered(cfg, case, mesh, microbatches: int = 4,
                  grad_dtype: str = "float32", fsdp="zero3",
                  srules_override=None):
    """Lower the cell's step function under the mesh with full shardings.

    ``fsdp``: "zero3" (params+moments fully sharded; per-layer gathers),
    "zero1" (params TP-only for compute, moments fully sharded), or
    "tp"/False (pure tensor parallelism).  True maps to "zero3".
    """
    model = Model(cfg)
    bspec = batch_specs(cfg, case)
    bshard = batch_shardings(bspec, mesh)
    if fsdp is True:
        fsdp = "zero3"
    if fsdp is False:
        fsdp = "tp"
    with mesh:
        if case.kind == "train":
            mb = microbatches if case.batch % microbatches == 0 else 1
            tc = TrainConfig(microbatches=mb, grad_dtype=grad_dtype)
            step = make_train_step(model, tc, mesh)
            if fsdp == "zero3":
                sshard = state_shardings(model, mesh, shr.FSDP_RULES)
            elif fsdp == "zero3_outdim":
                sshard = state_shardings(model, mesh, shr.MOE_FSDP_OUTDIM)
            elif fsdp == "zero1":
                sshard = state_shardings(model, mesh, None,
                                         opt_rules=shr.FSDP_RULES)
            else:
                sshard = state_shardings(model, mesh)
            return jax.jit(
                step,
                in_shardings=(sshard, bshard),
            ).lower(state_struct(model), bspec)
        srules = srules_override if srules_override is not None \
            else serve_rules(model, mesh)
        pstruct = serve_param_struct(model)
        pshard = param_shardings(model, mesh, srules)
        if case.kind == "prefill":
            return jax.jit(
                model.prefill,
                in_shardings=(pshard, bshard),
            ).lower(pstruct, bspec)
        # decode
        cstruct = model.cache_shapes(case.batch, case.seq)
        cshard = cache_shardings(cstruct, mesh)
        tokens = jax.ShapeDtypeStruct((case.batch, 1), jnp.int32)
        tshard = batch_shardings({"tokens": tokens}, mesh)["tokens"]
        return jax.jit(
            model.decode,
            in_shardings=(pshard, cshard, tshard, NamedSharding(mesh, P())),
        ).lower(pstruct, cstruct, tokens,
                jax.ShapeDtypeStruct((), jnp.int32))


def lower_cell(arch: str, shape: str, multi_pod: bool,
               remat: Optional[str] = None, probe: bool = True,
               microbatches: int = 4) -> Dict:
    cfg = get_arch(arch)
    if remat:
        import dataclasses
        cfg = dataclasses.replace(cfg, remat_policy=remat)
    case = SHAPES[shape]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    # decide serving rules on the FULL config once, so the reduced-depth
    # probes lower under the same sharding strategy as the main cell
    srules = serve_rules(Model(cfg), mesh) or dict(shr.DEFAULT_RULES)
    build = functools.partial(build_lowered, srules_override=srules)
    lowered = build(cfg, case, mesh, microbatches=microbatches)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    raw = rl.analyze(compiled, cfg, case, n_dev)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "roofline_raw": raw.as_dict(),
    }
    if probe:
        # scan-corrected totals (see costprobe.py): this is the §Roofline row
        from .costprobe import probe_costs
        pc = probe_costs(cfg, case, mesh, build)
        corr = rl.Roofline(
            flops=pc["flops"], bytes_accessed=pc["bytes"],
            coll_bytes=rl.weighted_collective_bytes(pc["collectives"]),
            per_op={k: int(v) for k, v in pc["collectives"].items()},
            n_devices=n_dev,
            model_flops_per_device=rl.model_flops(cfg, case, n_dev))
        rec["roofline"] = corr.as_dict()
        rec["probe_points"] = pc["probe_points"]
    else:
        rec["roofline"] = rec["roofline_raw"]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--remat", default=None)
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--no-probe", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skipped")}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = (arch, shape, "multi" if mp else "single")
                if args.skip_done and key in done:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape, mp, remat=args.remat,
                                     probe=not args.no_probe,
                                     microbatches=args.microbatches)
                except Exception as e:   # a failure here is a bug: record it
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": str(e)[:2000],
                           "trace": traceback.format_exc()[-2000:]}
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"]) != key]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                if rec["status"] == "ok":
                    m = rec["memory"]
                    r = rec["roofline"]
                    print(f"  ok: compile {rec['compile_s']}s  "
                          f"args {m['argument_bytes']/2**30:.2f} GiB/dev  "
                          f"temp {m['temp_bytes']/2**30:.2f} GiB/dev  "
                          f"dominant={r['dominant']}  "
                          f"roofline_frac={r['roofline_fraction']:.3f}",
                          flush=True)
                else:
                    print(f"  {rec['status']}: "
                          f"{rec.get('reason', rec.get('error', ''))[:200]}",
                          flush=True)


if __name__ == "__main__":
    main()
