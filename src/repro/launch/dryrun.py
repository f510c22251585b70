"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.  For every (arch x shape x mesh) cell this lowers + compiles the
real train/prefill/decode step against ShapeDtypeStruct inputs (no
allocation), prints memory/cost analysis, and records the collective
schedule for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k
  python -m repro.launch.dryrun --arch rwkv6-3b --shape long_500k --multi-pod
  python -m repro.launch.dryrun --list
"""
# The next lines MUST run before any other import (jax locks the platform
# and device count on first init): the dry run compiles for forced host
# devices, so it is pinned to the CPU and never takes an accelerator.
# REPRO_DRYRUN_DEVICES overrides the device count for small CI runs.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
from typing import Dict, Optional      # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs import SHAPES, cells, get_config, get_shape   # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402
from repro.models.api import build_model                         # noqa: E402
from repro.sharding.specs import MeshSharder, SpecBuilder        # noqa: E402
from repro.train.optim import adamw_init, adamw_update, clip_by_global_norm  # noqa: E402

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _sds(tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def auto_microbatches(arch: str, shape, mesh) -> int:
    """Split the per-device token budget so stored inter-layer activations
    (bf16 carries saved for the remat backward) stay within ~2 GB/device
    (perf iteration 2, EXPERIMENTS.md §Perf)."""
    cfg = get_config(arch)
    n_data = int(np.prod([v for k, v in mesh.shape.items() if k != "model"]))
    tokens_per_dev = shape.global_batch * shape.seq_len / max(n_data, 1)
    carry_bytes = tokens_per_dev * cfg.d_model * 2 * cfg.n_layers
    # 6 GB activation-carry budget: fewer microbatches = fewer per-microbatch
    # FSDP param regathers (perf iteration 2b, EXPERIMENTS.md §Perf)
    m = max(int(np.ceil(carry_bytes / 6e9)), 1)
    # keep the microbatch count a divisor of the per-device batch
    b_per_dev = max(shape.global_batch // max(n_data, 1), 1)
    while b_per_dev % m:
        m += 1
    return min(m, b_per_dev)


def train_policy(cfg, shape, mesh) -> str:
    """Sharding policy per (family, step) — DESIGN.md §5 / §Perf iter 4:
      * MoE training keeps 'tp' (expert parallelism over 'model');
      * recurrent archs (rwkv/rg-lru) cannot shard the sequence ->
        'fsdp_batch' when the batch covers every device, else 'tp';
      * dense-attention training uses 'fsdp_sp' (batch over data axes,
        sequence over 'model', fully-FSDP params: no TP all-reduces)."""
    total = int(np.prod(list(mesh.shape.values())))
    if cfg.moe is not None or cfg.family == "rnnt":
        return "tp"
    kinds = set(cfg.layer_kinds())
    if kinds & {"rec", "rwkv"}:
        return "fsdp_batch" if shape.global_batch % total == 0 else "tp"
    return "fsdp_sp"


def build_step(arch: str, shape_name: str, mesh, step: Optional[str] = None,
               microbatches: Optional[int] = None,
               policy: Optional[str] = None):
    """Returns (fn, example_args as sharded ShapeDtypeStructs)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    bundle = build_model(cfg)
    step = step or shape.kind
    if policy is None:
        if step in ("train", "select"):
            policy = train_policy(cfg, shape, mesh)
        elif step == "prefill" and cfg.moe is None and not (
                set(cfg.layer_kinds()) & {"rec", "rwkv"}):
            # prefill is throughput-oriented forward-only work: sequence
            # sharding beats TP for it just as in training (§Perf iter 6);
            # recurrent archs keep TP (sequence cannot shard)
            policy = "fsdp_sp"
        else:
            policy = "tp"
    sb = SpecBuilder(mesh, mode=policy)
    sharder = MeshSharder(mesh, mode=policy)

    params_shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    params_sh = sb.to_shardings(sb.param_specs(params_shapes))
    params_sds = _sds(params_shapes, params_sh)

    batch_shapes = bundle.input_specs(shape)
    batch_sh = sb.to_shardings(sb.batch_specs(batch_shapes))
    batch_sds = _sds(batch_shapes, batch_sh)

    if step == "train":
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        opt_sh = sb.to_shardings(sb.param_specs(opt_shapes))
        opt_sds = _sds(opt_shapes, opt_sh)
        # fsdp policies shard tokens over (nearly) all devices: the stored
        # activation carry is tiny, no microbatching needed
        if microbatches is None:
            mb = (auto_microbatches(arch, shape, mesh)
                  if policy == "tp" else 1)
        else:
            mb = microbatches

        def grads_of(params, batch):
            def loss(p):
                total, metrics = bundle.loss_fn(p, batch, shard=sharder)
                return total, metrics
            (_, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params)
            return grads, metrics

        def cast_working(params):
            """bf16 working copy, cast shard-local BEFORE any FSDP gather
            (halves param-gather wire; grads come back bf16 -> bf16
            gradient reduction; optimizer applies them to fp32 masters).
            Perf iteration 5, EXPERIMENTS.md §Perf."""
            dt = jnp.dtype(get_config(arch).compute_dtype)
            if dt == jnp.float32:
                return params
            return jax.tree.map(
                lambda p, s: jax.lax.with_sharding_constraint(
                    p.astype(dt) if p.dtype == jnp.float32 else p, s),
                params, params_sh)

        def train_step(params, opt_state, batch):
            working = cast_working(params)
            if mb <= 1:
                grads, metrics = grads_of(working, batch)
                # pin grads to the param sharding: XLA emits reduce-scatter
                # into FSDP shards instead of a full all-reduce (§Perf)
                grads = jax.tree.map(
                    lambda g, s: jax.lax.with_sharding_constraint(
                        g.astype(jnp.float32), s),
                    grads, params_sh)
            else:
                # gradient accumulation: activation live-set shrinks by mb,
                # gradient all-reduce happens once on the accumulated sum
                micro = jax.tree.map(
                    lambda a: a.reshape((mb, a.shape[0] // mb) + a.shape[1:]),
                    batch)

                def acc_step(carry, mbatch):
                    g_acc = carry
                    g, metrics = grads_of(working, mbatch)
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                    return g_acc, metrics

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                grads, metrics_all = jax.lax.scan(acc_step, g0, micro)
                grads = jax.tree.map(lambda g: g / mb, grads)
                metrics = jax.tree.map(lambda m: m.mean(), metrics_all)
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            params, opt_state = adamw_update(params, grads, opt_state,
                                             lr=1e-4)
            return params, opt_state, dict(metrics, grad_norm=gnorm)

        fn = jax.jit(train_step, out_shardings=(params_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        return fn, (params_sds, opt_sds, batch_sds)

    if step == "prefill":
        def prefill_step(params, batch):
            return bundle.prefill(params, batch, shard=sharder)
        fn = jax.jit(prefill_step)
        return fn, (params_sds, batch_sds)

    if step == "select":
        # the paper's selection round (stage A sketching + stage B
        # partitioned OMP) over one candidate chunk of `global_batch`
        # units of `unit_size` examples each
        from repro.core.lastlayer import units_gradients_batched
        from repro.core.pgm import partitioned_gm
        from repro.core.sketch import Projections
        unit_size = 4
        n_units = shape.global_batch
        D_parts = 16
        budget = max(int(0.3 * n_units) // D_parts, 1)
        k1 = k2 = 64

        unit_specs = {
            k: jax.ShapeDtypeStruct((n_units,) + v.shape, v.dtype)
            for k, v in bundle.input_specs(
                type(shape)(shape.name, shape.seq_len, unit_size,
                            "train")).items()}
        units_sh = sb.to_shardings(sb.batch_specs(unit_specs))
        units_sds = _sds(unit_specs, units_sh)
        proj_specs = (jax.ShapeDtypeStruct((cfg.d_model, k1), jnp.float32),
                      jax.ShapeDtypeStruct((cfg.vocab_size, k2),
                                           jnp.float32))
        psh = sb.to_shardings((sb.param_spec(".proj_h", proj_specs[0].shape),
                               sb.param_spec(".proj_v", proj_specs[1].shape)))
        proj_sds = tuple(_sds(s, h) for s, h in zip(proj_specs, psh))

        def select_step(params, units, r_h, r_v):
            g = units_gradients_batched(bundle, params, units,
                                        Projections(r_h, r_v),
                                        shard=sharder)
            return partitioned_gm(g, D_parts, budget)

        fn = jax.jit(select_step)
        return fn, (params_sds, units_sds) + proj_sds

    if step == "decode":
        B = shape.global_batch
        cache_shapes = jax.eval_shape(
            lambda: bundle.init_cache(B, shape.seq_len))
        cache_sh = sb.to_shardings(sb.cache_specs(cache_shapes, B))
        cache_sds = _sds(cache_shapes, cache_sh)

        def decode_step(params, cache, tokens):
            return bundle.decode(params, cache, tokens, shard=sharder)

        fn = jax.jit(decode_step, donate_argnums=(1,),
                     out_shardings=(None, cache_sh))
        return fn, (params_sds, cache_sds, batch_sds["tokens"])

    raise ValueError(step)


# ---------------------------------------------------------------------------
# Compiled-artifact analysis
# ---------------------------------------------------------------------------

def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum output bytes + estimated per-chip wire bytes for each collective
    op in the (post-SPMD) optimized HLO.  Wire-byte model per op:
      all-reduce      2*size*(g-1)/g      (ring AR, size = buffer bytes)
      all-gather      size*(g-1)/g        (size = output bytes)
      reduce-scatter  size*(g-1)         ~= input traffic, size = out bytes
      all-to-all      size*(g-1)/g
      collective-permute  size
    """
    out: Dict[str, Dict[str, float]] = {
        c: {"count": 0, "bytes": 0.0, "wire_bytes": 0.0} for c in COLLECTIVES}
    shape_re = re.compile(r"=\s*(?:\(([^)]*)\)|((?:f|bf|s|u|pred)[\w]*\[[^\]]*\]))\s*([\w-]+)")
    tensor_re = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")
    group_re = re.compile(r"replica_groups=\{\{([^}]*)\}")
    iota_re = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = shape_re.search(stripped)
        if not m:
            continue
        op = m.group(3)
        base = None
        for c in COLLECTIVES:
            if op == c or op.startswith(c + "-") or op == c + "-start":
                base = c
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue
        shapes_str = m.group(1) if m.group(1) else m.group(2)
        nbytes = 0.0
        for t in tensor_re.finditer(shapes_str):
            dt, dims = t.group(1), t.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES.get(dt, 4)
        g = 1
        gm = iota_re.search(stripped)
        if gm:
            g = int(gm.group(2))
        else:
            gm = group_re.search(stripped)
            if gm:
                g = len(gm.group(1).split(","))
        g = max(g, 1)
        if base == "all-reduce":
            wire = 2.0 * nbytes * (g - 1) / g
        elif base in ("all-gather", "all-to-all"):
            wire = nbytes * (g - 1) / g
        elif base == "reduce-scatter":
            wire = nbytes * (g - 1)
        else:
            wire = nbytes
        out[base]["count"] += 1
        out[base]["bytes"] += nbytes
        out[base]["wire_bytes"] += wire
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             step: Optional[str] = None, out_path: Optional[str] = None,
             verbose: bool = True, microbatches: Optional[int] = None,
             policy: Optional[str] = None) -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args = build_step(arch, shape_name, mesh, step,
                          microbatches=microbatches, policy=policy)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        ca = compiled.cost_analysis() or {}
    except Exception as e:   # backend may not support it
        ca = {"error": str(e)}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                ma, "generated_code_size_in_bytes", None),
        }
    except Exception as e:
        ma, mem = None, {"error": str(e)}

    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    # multiplicity-corrected per-device analysis (XLA cost_analysis counts
    # while bodies once; our programs are scan-heavy — see hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze as hlo_analyze
    corrected = hlo_analyze(hlo)

    cfgx = get_config(arch)
    eff_policy = policy or (train_policy(
        cfgx, get_shape(shape_name),
        mesh) if (step or get_shape(shape_name).kind) == "train" else "tp")
    result = {
        "arch": arch,
        "shape": shape_name,
        "step": step or get_shape(shape_name).kind,
        "policy": eff_policy,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "flops": corrected.flops,
        "bytes_accessed": corrected.bytes,
        "flops_xla_raw": ca.get("flops"),
        "bytes_xla_raw": ca.get("bytes accessed"),
        "cost_analysis": {k: v for k, v in ca.items()
                          if isinstance(v, (int, float))},
        "memory": mem,
        "collectives": corrected.collectives,
        "collectives_raw_once": colls,
        "lower_s": t_lower,
        "compile_s": t_compile,
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {result['mesh']} "
              f"({result['step']}) OK — lower {t_lower:.1f}s, "
              f"compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  flops={result['flops']}, bytes={result['bytes_accessed']}")
        tot_wire = sum(c["wire_bytes"] for c in colls.values())
        print("  collectives: " + ", ".join(
            f"{k}:{v['count']} ({v['bytes']/1e6:.1f} MB out, "
            f"{v['wire_bytes']/1e6:.1f} MB wire)"
            for k, v in colls.items() if v["count"]) +
            f" | total wire {tot_wire/1e6:.1f} MB")
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--step", default=None,
                    help="train|prefill|decode (default: shape kind)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--policy", default=None,
                    choices=[None, "tp", "fsdp_sp", "fsdp_batch"])
    args = ap.parse_args()
    if args.list:
        for arch, shape, status in cells(include_skips=True):
            print(f"{arch:24s} {shape:12s} {status}")
        return
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod, step=args.step,
             out_path=args.out, microbatches=args.microbatch,
             policy=args.policy)


if __name__ == "__main__":
    main()
