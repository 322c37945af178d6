"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: jit with explicit in/out shardings over the production mesh,
`.lower().compile()` must succeed, and the compiled artifact yields
memory_analysis / cost_analysis / the collective schedule for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""

# The forced 512-device CPU platform MUST be configured before jax (or any
# repro module that imports jax) initializes the backend.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.configs.shapes import get_shape, shapes_for
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import model_zoo as zoo
from repro.optim.adam import AdamConfig, adam_init
from repro.train.options import PerfOptions
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step

# --- TPU v5e hardware constants (roofline targets; container runs on CPU) ---
PEAK_FLOPS = 197e12   # bf16 FLOP/s per chip
HBM_BW = 819e9        # bytes/s per chip
ICI_BW = 50e9         # bytes/s per link (per-chip collective bandwidth unit)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<rtype>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str):
    """Per-chip bytes moved by collectives, parsed from partitioned HLO.

    Result shapes in post-SPMD HLO are per-device. Bytes-moved model (ring):
      all-reduce        2 * R * (g-1)/g
      all-gather        R * (g-1)/g          (R = gathered result)
      reduce-scatter    R * (g-1)            (R = scattered result)
      all-to-all        R * (g-1)/g
      collective-perm.  R
    """
    per_op = {}
    total = 0.0
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        r = _shape_bytes(m.group("rtype"))
        tail = hlo_text[m.end() : m.end() + 2000]
        g = 2
        mg = _GROUPS_RE.search(tail)
        if mg:
            g = max(2, mg.group(1).count(",") + 1)
        else:
            mg = _GROUPS_IOTA_RE.search(tail)
            if mg:
                g = max(2, int(mg.group(2)))
        if op == "all-reduce":
            moved = 2 * r * (g - 1) / g
        elif op == "all-gather":
            moved = r * (g - 1) / g
        elif op == "reduce-scatter":
            moved = r * (g - 1)
        elif op == "all-to-all":
            moved = r * (g - 1) / g
        else:
            moved = float(r)
        key = op
        per_op.setdefault(key, {"count": 0, "bytes": 0.0})
        per_op[key]["count"] += 1
        per_op[key]["bytes"] += moved
        total += moved
    return total, per_op


def build_cell(arch: str, shape_name: str, mesh, moment_dtype=None, options=None):
    """Lower one (arch, shape) cell on `mesh`. Returns (jitted, args) specs."""
    options = options or PerfOptions()
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape.name == "long_500k" and not cfg.supports_long_context():
        raise ValueError(f"{arch} skips long_500k (full attention; DESIGN.md §5)")

    params_spec = jax.eval_shape(lambda k: zoo.init_params(cfg, k), jax.random.PRNGKey(0))
    serve = options.serve_sharding and shape.kind in ("prefill", "decode")
    params_sh = shd.params_shardings(cfg, params_spec, mesh, serve=serve)

    specs = zoo.input_specs(cfg, shape)

    if shape.kind == "train":
        # bf16 Adam moments for the 671B config: fp32 moments exceed 16 GB/chip
        # on the single pod (see EXPERIMENTS.md §Dry-run).
        mdt = moment_dtype or (jnp.bfloat16 if arch == "deepseek-v3-671b" else jnp.float32)
        ocfg = AdamConfig(moment_dtype=mdt)
        opt_spec = jax.eval_shape(lambda p: adam_init(ocfg, p), params_spec)
        opt_sh = type(opt_spec)(
            m=shd.params_shardings(cfg, opt_spec.m, mesh),
            v=shd.params_shardings(cfg, opt_spec.v, mesh),
            step=shd.replicated(mesh),
        )
        batch_sh = shd.batch_shardings(specs["batch"], mesh)
        step_fn = make_train_step(cfg, ocfg, options)
        metrics_sh = {k: shd.replicated(mesh) for k in ("loss", "aux_loss", "grad_norm", "lr")}
        jitted = jax.jit(
            step_fn,
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, metrics_sh),
            donate_argnums=(0, 1),
        )
        args = (params_spec, opt_spec, specs["batch"])
    elif shape.kind == "prefill":
        batch_sh = shd.batch_shardings(specs["batch"], mesh)
        step_fn = make_prefill_step(cfg, options)
        caches_spec = jax.eval_shape(
            lambda p, b: step_fn(p, b)[1], params_spec, specs["batch"]
        )
        caches_sh = shd.cache_shardings(caches_spec, mesh)
        logits_sh = shd.batch_shardings(
            jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab_size), jnp.float32), mesh
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(params_sh, batch_sh),
            out_shardings=(logits_sh, caches_sh),
        )
        args = (params_spec, specs["batch"])
    else:  # decode
        step_fn = make_decode_step(cfg, options)
        caches_sh = shd.cache_shardings(specs["caches"], mesh)
        token_sh = shd.batch_shardings(specs["token"], mesh)
        logits_sh = shd.batch_shardings(
            jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab_size), jnp.float32), mesh
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(params_sh, token_sh, caches_sh, shd.replicated(mesh)),
            out_shardings=(logits_sh, caches_sh, shd.replicated(mesh)),
            donate_argnums=(2,),
        )
        args = (params_spec, specs["token"], specs["caches"], jax.ShapeDtypeStruct((), jnp.int32))
    return cfg, shape, jitted, args


_WHILE_RE = re.compile(r"=\s*\([^)]*\)\s*while\(|=\s*[a-z0-9]+\[[0-9,]*\][^ ]*\s*while\(")


def _compile_and_measure(arch, shape_name, mesh, options):
    """One compile -> (cfg, shape, flops, bytes, coll_bytes, per_op, ma, has_loop)."""
    cfg, shape, jitted, args = build_cell(arch, shape_name, mesh, options=options)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll_bytes, coll_per_op = collective_stats(hlo)
    has_loop = bool(_WHILE_RE.search(hlo))
    return (cfg, shape, float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), coll_bytes, coll_per_op, ma,
            has_loop)


def _loop_trip_count(cfg):
    """Units of the (equal-sized) scan loops left after partial unroll."""
    from repro.models.transformer import FULL_UNROLL_THRESHOLD, decoder_plan

    counts = {c for c, _ in decoder_plan(cfg) if c > FULL_UNROLL_THRESHOLD}
    if cfg.is_encoder_decoder and cfg.num_encoder_layers > FULL_UNROLL_THRESHOLD:
        counts.add(cfg.num_encoder_layers)
    if not counts:
        return 0
    assert len(counts) == 1, f"unequal loop counts {counts}: extrapolation invalid"
    return counts.pop()


def run_cell(arch: str, shape_name: str, multi_pod: bool, options=None,
             exact: bool = True):
    """Compile one cell; return the roofline record.

    exact=True compiles twice (scan unroll u=1, u=2) and extrapolates the
    exact per-step FLOPs/bytes/collective bytes: XLA cost analysis counts a
    while body once, so f(u) = base + u * per_unit and
    true = f1 + (C - 1) * (f2 - f1) for a C-unit loop.
    """
    options = options or PerfOptions()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    # First compile at u=2 (a two-unit loop body is large enough that XLA does
    # not silently unroll the while loop itself, which would break the model).
    o2 = dataclasses.replace(options, scan_unroll=2)
    cfg, shape, flops2, bytes2, coll2, per_op2, ma, loop2 = _compile_and_measure(
        arch, shape_name, mesh, o2)
    C = _loop_trip_count(cfg)
    extrapolated = False
    if exact and C > 3 and loop2:
        o3 = dataclasses.replace(options, scan_unroll=3)
        _, _, flops3, bytes3, coll3, per_op3, _, loop3 = _compile_and_measure(
            arch, shape_name, mesh, o3)
        if loop3:
            # f(u) = base + u*p with the loop body counted once =>
            # exact = f2 + (C - 2) * (f3 - f2).
            k = C - 2
            flops = flops2 + k * (flops3 - flops2)
            bytes_accessed = bytes2 + k * (bytes3 - bytes2)
            coll_bytes = coll2 + k * (coll3 - coll2)
            coll_per_op = {}
            for op in set(per_op2) | set(per_op3):
                b2 = per_op2.get(op, {"bytes": 0.0, "count": 0})
                b3 = per_op3.get(op, {"bytes": 0.0, "count": 0})
                coll_per_op[op] = {
                    "count": b2["count"] + k * (b3["count"] - b2["count"]),
                    "bytes": b2["bytes"] + k * (b3["bytes"] - b2["bytes"]),
                }
            extrapolated = True
        else:
            # u=3 got fully unrolled by XLA: its counts are already exact.
            flops, bytes_accessed, coll_bytes, coll_per_op = flops3, bytes3, coll3, per_op3
    else:
        # No loop left (small model or XLA unrolled it): counts are exact.
        flops, bytes_accessed, coll_bytes, coll_per_op = flops2, bytes2, coll2, per_op2
    t_compile = time.time() - t0
    t_lower = 0.0
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_coll = coll_bytes / ICI_BW
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    mf = zoo.model_flops(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "options": {
            "sharded_loss": options.sharded_loss,
            "remat_policy": options.remat_policy,
            "zero3_gather": options.zero3_gather,
            "serve_sharding": options.serve_sharding,
        },
        "status": "ok",
        "exact_accounting": extrapolated or not loop2,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_chip": {
            "hlo_flops": flops,
            "hlo_bytes": bytes_accessed,
            "collective_bytes": coll_bytes,
            "collectives": coll_per_op,
            "memory_analysis": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
        },
        "roofline": {
            "compute_s": t_compute,
            "memory_s": t_memory,
            "collective_s": t_coll,
            "dominant": dominant,
            "model_flops_global": mf,
            "model_flops_per_chip": mf / chips,
            "useful_flop_ratio": (mf / chips) / flops if flops else 0.0,
            "roofline_fraction": ((mf / chips) / PEAK_FLOPS)
            / max(t_compute, t_memory, t_coll)
            if max(t_compute, t_memory, t_coll) > 0
            else 0.0,
        },
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every cell (both meshes)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--sharded-loss", action="store_true")
    ap.add_argument("--zero3-gather", action="store_true")
    ap.add_argument("--serve-sharding", action="store_true")
    ap.add_argument("--attn-seq-shard", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="per-arch optimized recipe (EXPERIMENTS.md §Perf): "
                         "sharded_loss + zero3_gather + dots remat (+ "
                         "sequence-sharded attention when heads don't divide TP)")
    ap.add_argument("--remat", default="full", choices=("full", "dots", "none"))
    ap.add_argument("--no-exact", action="store_true",
                    help="single u=1 compile; loop bodies counted once (fast, "
                         "undercounts per-layer cost by the trip count)")
    ap.add_argument("--force", action="store_true", help="overwrite existing JSONs")
    args = ap.parse_args()
    options = PerfOptions(sharded_loss=args.sharded_loss, remat_policy=args.remat,
                          zero3_gather=args.zero3_gather,
                          serve_sharding=args.serve_sharding,
                          attn_seq_shard=args.attn_seq_shard)

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in shapes_for(get_config(arch)):
                for mp in (False, True):
                    cells.append((arch, shape.name, mp))
    else:
        cells = [(args.arch, args.shape, args.multi_pod)]

    failures = 0
    for arch, shape_name, mp in cells:
        if args.opt:
            cfg_a = get_config(arch)
            seq_shard = bool(cfg_a.num_heads) and (
                cfg_a.num_heads % 16 != 0 or cfg_a.num_kv_heads % 16 != 0
            ) and not cfg_a.use_mla
            options = PerfOptions(
                sharded_loss=True, zero3_gather=True, remat_policy="dots",
                attn_seq_shard=seq_shard,
            )
        tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
        out_path = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_path) and not args.force:
            print(f"[skip] {tag} (exists)")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            rec = run_cell(arch, shape_name, mp, options=options,
                           exact=not args.no_exact)
            r = rec["roofline"]
            print(
                f"  ok: compute={r['compute_s']*1e3:.1f}ms memory={r['memory_s']*1e3:.1f}ms "
                f"collective={r['collective_s']*1e3:.1f}ms dominant={r['dominant']} "
                f"roofline_frac={r['roofline_fraction']:.3f} "
                f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — record and continue
            failures += 1
            rec = {
                "arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
            print(f"  FAILED: {type(e).__name__}: {str(e)[:300]}", flush=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
