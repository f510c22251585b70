"""Training launcher: mesh + sharding policy around Algorithm 1.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b-smoke
      --method pgm --epochs 6 [--engine scan|host] [--mesh 2x4]
      [--mesh-axes data,pod --compress-mode bf16|topk]
      [--epoch-chunk 4] [--resident-selection] [--ckpt DIR] [--resume]
      [--noise 0.2 --snr-db 5]

``launch_train`` is the programmatic entry point the examples and
benchmarks share.  With ``--mesh DATAxMODEL`` the *whole* training run
is mesh-native (DESIGN.md §5): the scanned epoch engine device_puts the
selection units sharded over ``data``, compiles the epoch scan with
FSDP/TP param shardings from ``sharding/specs.py`` and data-sharded
batches, and PGM selection (stage A GSPMD, stage B
``pgm_select_sharded``) reuses the same sharded unit buffers — one code
path on 1 and N devices.  ``--epoch-chunk N`` folds N bucketed epochs
into one dispatch with on-device validation/newbob, and plan prefetch
overlaps host-side plan generation with the running chunk.  On CPU
without a mesh it runs the smoke-scale loop for development and CI.

``--noise``/``--snr-db`` inject the paper's robustness setting into the
synthetic corpora: a ``noise`` fraction of training utterances gets
additive feature noise at ``snr_db`` (ASR) or corrupted labels (LM),
and validation matching (``Val=True``) turns on automatically so PGM
selects against the clean validation gradient.
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax

from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import asr_units, lm_units
from repro.data.synthetic import make_asr_corpus, make_lm_corpus
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.train.loop import History, train_with_selection


def parse_mesh(spec: Optional[str], axes: str = "data,model"):
    """'2x4' -> a 2-axis mesh; None/'' -> no mesh (single device).

    ``axes`` names the two mesh axes (comma-separated).  The default
    ``data,model`` is the GSPMD FSDP/TP training mesh; ``data,pod``
    builds the two-level mesh whose slow ``pod`` axis carries the
    explicit compressed gradient collective (``--compress-mode``,
    DESIGN.md §5)."""
    if not spec:
        return None
    dims = tuple(int(x) for x in spec.lower().split("x"))
    names = tuple(a.strip() for a in axes.split(","))
    if len(dims) != 2 or len(names) != 2:
        raise ValueError(f"mesh spec must be AxB over two named axes, "
                         f"got {spec!r} over {axes!r}")
    return make_mesh(dims, names)


def make_units_for(cfg, *, n: int, seq: int, noise: float, seed: int = 0,
                   unit_size: int = 4, snr_db: float = 10.0):
    """(train units, val units) for the arch family — RNN-T gets the ASR
    corpus, everything else the LM corpus.  ``noise`` corrupts that
    fraction of *training* examples (additive feature noise at
    ``snr_db`` for ASR, label corruption for LM); validation stays
    clean, as in the paper's robustness setting."""
    if cfg.family == "rnnt":
        r = cfg.rnnt
        corpus = make_asr_corpus(seed, n, n_feats=r.n_feats,
                                 vocab_size=r.vocab_size,
                                 noise_fraction=noise, snr_db=snr_db)
        vc = make_asr_corpus(seed + 7, max(n // 4, 8), n_feats=r.n_feats,
                             vocab_size=r.vocab_size)
        return asr_units(corpus, unit_size), asr_units(vc, unit_size)
    corpus = make_lm_corpus(seed, n, seq, cfg.vocab_size,
                            noise_fraction=noise)
    vc = make_lm_corpus(seed + 7, max(n // 4, 8), seq, cfg.vocab_size)
    return lm_units(corpus, unit_size), lm_units(vc, unit_size)


def launch_train(
    arch: str,
    tc: TrainConfig,
    *,
    method: str = "pgm",
    engine: str = "scan",
    resident_selection: bool = False,
    mesh=None,
    data_axis: str = "data",
    spec_mode: str = "tp",
    epoch_chunk: int = 1,
    plan_prefetch: bool = True,
    n: int = 96,
    seq: int = 24,
    noise: float = 0.0,
    snr_db: float = 10.0,
    batch_units: int = 1,
    loss_impl: Optional[str] = None,
    ckpt_dir: Optional[str] = None,
    resume: bool = False,
    log_fn=print,
) -> History:
    cfg = get_config(arch)
    if loss_impl is not None and cfg.family == "rnnt":
        import dataclasses
        cfg = dataclasses.replace(
            cfg, rnnt=dataclasses.replace(cfg.rnnt, loss_impl=loss_impl))
    bundle = build_model(cfg)
    units, val = make_units_for(cfg, n=n, seq=seq, noise=noise,
                                seed=tc.seed, snr_db=snr_db)
    # unit placement (data-sharded on a mesh) is owned by the engine
    return train_with_selection(
        bundle, units, tc, method=method, val_units=val,
        batch_units=batch_units, ckpt_dir=ckpt_dir, resume=resume,
        engine=engine, resident_selection=resident_selection, mesh=mesh,
        data_axis=data_axis, spec_mode=spec_mode, epoch_chunk=epoch_chunk,
        plan_prefetch=plan_prefetch, log_fn=log_fn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--method", default="pgm")
    ap.add_argument("--engine", default="scan", choices=["scan", "host"])
    ap.add_argument("--resident-selection", action="store_true",
                    help="PGM stage A as one jitted batch-scanned pass "
                         "over the device-resident units (no host "
                         "round-trip per selection round)")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL, e.g. 2x4 (default: no mesh); shards "
                         "the epoch engine, units and selection")
    ap.add_argument("--mesh-axes", default="data,model",
                    help="names of the two mesh axes; 'data,pod' builds "
                         "the two-level data x pod mesh whose slow axis "
                         "runs the explicit compressed gradient "
                         "collective (DESIGN.md §5)")
    ap.add_argument("--compress-mode", default="none",
                    choices=["none", "bf16", "topk"],
                    help="cross-pod gradient compressor on the 'pod' "
                         "mesh axis (train/compress.py): bf16 halves "
                         "the collective's wire width, topk sends the "
                         "k largest entries per leaf with error "
                         "feedback; requires --mesh-axes data,pod")
    ap.add_argument("--compress-k-frac", type=float, default=0.05,
                    help="top-k fraction per gradient leaf for "
                         "--compress-mode topk")
    ap.add_argument("--spec-mode", default="tp",
                    choices=["tp", "fsdp_sp", "fsdp_batch"],
                    help="SpecBuilder param-sharding policy for the "
                         "training carry (DESIGN.md §5)")
    ap.add_argument("--epoch-chunk", type=int, default=1,
                    help="fold up to N epochs into one scan dispatch "
                         "(on-device validation/newbob; metrics fetched "
                         "once per chunk)")
    ap.add_argument("--no-plan-prefetch", action="store_true",
                    help="build epoch plans synchronously instead of on "
                         "the prefetch thread")
    ap.add_argument("--subset", type=float, default=0.3)
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--select-every", type=int, default=5)
    ap.add_argument("--warm-start", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--n", type=int, default=96)
    ap.add_argument("--seq", type=int, default=24)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--noise", type=float, default=0.0,
                    help="fraction of corrupted training examples "
                         "(feature noise for ASR, label noise for LM)")
    ap.add_argument("--snr-db", type=float, default=10.0,
                    help="SNR of the injected ASR feature noise (dB); "
                         "only meaningful with --noise > 0 on an RNN-T "
                         "arch")
    ap.add_argument("--loss-impl", default=None,
                    choices=["fused", "dense"],
                    help="RNN-T loss path (DESIGN.md §2): fused "
                         "custom_vjp lattice (default) or the dense "
                         "autodiff parity oracle")
    ap.add_argument("--exact-gradients", action="store_true",
                    help="paper-faithful exact last-layer gradients "
                         "(no sketching)")
    ap.add_argument("--selection-kernels", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="selection-round kernel backend "
                         "(PGMConfig.kernel_impl): fused Pallas "
                         "grad-sketch + Gram kernels vs the XLA "
                         "streamed paths; auto = pallas on TPU only")
    ap.add_argument("--nonfinite-guard", action="store_true",
                    help="gate NaN/Inf steps off inside the jitted step "
                         "(bit-exact no-op, no host sync) and count them "
                         "in the epoch metrics (DESIGN.md §10)")
    ap.add_argument("--max-skipped-steps", type=int, default=0,
                    help="divergence watchdog: this many *consecutive* "
                         "guarded-off steps triggers a rollback to the "
                         "last good checkpoint with a re-keyed batch "
                         "plan (0 = never; requires --nonfinite-guard)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    tc = TrainConfig(
        lr=args.lr, optimizer=args.optimizer, epochs=args.epochs,
        seed=args.seed,
        compress_mode=args.compress_mode,
        compress_k_frac=args.compress_k_frac,
        nonfinite_guard=args.nonfinite_guard,
        max_skipped_steps=args.max_skipped_steps,
        pgm=PGMConfig(subset_fraction=args.subset,
                      n_partitions=args.partitions,
                      select_every=args.select_every,
                      warm_start_epochs=args.warm_start,
                      val_matching=args.noise > 0,
                      use_sketch=not args.exact_gradients,
                      kernel_impl=args.selection_kernels))
    h = launch_train(args.arch, tc, method=args.method, engine=args.engine,
                     resident_selection=args.resident_selection,
                     mesh=parse_mesh(args.mesh, args.mesh_axes),
                     spec_mode=args.spec_mode,
                     epoch_chunk=args.epoch_chunk,
                     plan_prefetch=not args.no_plan_prefetch,
                     n=args.n, seq=args.seq, noise=args.noise,
                     snr_db=args.snr_db, loss_impl=args.loss_impl,
                     ckpt_dir=args.ckpt, resume=args.resume)
    if h.val_loss:
        print(f"done: val {h.val_loss[-1]:.4f}, "
              f"cost {h.cost_units:.2f} epoch-units, "
              f"wall {h.wall_time:.1f}s on {jax.device_count()} device(s)")


if __name__ == "__main__":
    main()
