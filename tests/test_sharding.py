"""Sharding/distribution tests on an 8-host-device mesh (subprocess so the
main test process keeps its single device).  Exercises: SpecBuilder rules,
shard_map PGM stage B, compressed psum, and a reduced-config train-step
lower+compile per policy."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# every test here lowers+compiles in an 8-device subprocess — slow tier
pytestmark = pytest.mark.slow

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def test_spec_builder_rules():
    out = _run(textwrap.dedent("""
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.sharding.specs import SpecBuilder
        mesh = make_mesh((2, 4), ("data", "model"))
        sb = SpecBuilder(mesh)
        # wq: (d, q_dim) -> (fsdp, tp)
        assert sb.param_spec(".blocks.attn.wq", (64, 64)) == P("data", "model")
        # indivisible dims are left unsharded
        assert sb.param_spec(".blocks.attn.wq", (63, 64)) == P(None, "model")
        # stacked group params get a leading None
        assert sb.param_spec(".groups.attn.wq", (4, 64, 64)) == \
            P(None, "data", "model")
        # embed: vocab over model in tp mode
        assert sb.param_spec(".embed.w", (80, 64)) == P("model", "data")
        # moe experts over model when divisible
        s = sb.param_spec(".moe.w_in", (8, 64, 64))
        assert s == P("model", "data", None), s
        # fsdp_sp mode: no tp; params over all axes
        sb2 = SpecBuilder(mesh, mode="fsdp_sp")
        assert sb2.param_spec(".blocks.mlp.w_in", (64, 64)) == \
            P(("data", "model"), None)
        assert sb2.batch_spec("tokens", (16, 32)) == P("data", "model")
        print("SPECS-OK")
    """))
    assert "SPECS-OK" in out


def test_spec_builder_expert_mode():
    """Expert-axis mode (DESIGN.md §8): expert banks shard their leading
    E dim over ``expert`` (or fall back to fsdp without that axis),
    routers replicate, indivisible expert banks raise a ValueError that
    names the arch, and the pod axis never leaks into param specs."""
    out = _run(textwrap.dedent("""
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.sharding.specs import SpecBuilder
        mesh = make_mesh((2, 2, 2), ("data", "expert", "model"))
        sb = SpecBuilder(mesh, mode="expert", arch="mixtral-8x7b")
        # stacked expert banks: E over 'expert', d over fsdp, dff over tp
        s = sb.param_spec(".groups.moe.w_in", (2, 4, 64, 32))
        assert s == P(None, "expert", "data", "model"), s
        s = sb.param_spec(".moe.w_out", (4, 32, 64))
        assert s == P("expert", "model", "data"), s
        # routers replicate in expert mode
        assert sb.param_spec(".moe.router", (64, 4)) == P(None, None)
        # dense params keep the tp rules ('expert' never carries them)
        assert sb.param_spec(".blocks.attn.wq", (64, 64)) == \\
            P("data", "model")
        # indivisible expert banks fail loudly, naming the arch
        try:
            sb.param_spec(".moe.w_in", (3, 64, 32))
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "mixtral-8x7b" in str(e) and "expert" in str(e), e
        # no expert axis on the mesh: E falls back to the fsdp axis and
        # the d dim is left alone (never shard one axis twice)
        mesh2 = make_mesh((4, 2), ("data", "model"))
        sb2 = SpecBuilder(mesh2, mode="expert", arch="olmoe-1b-7b")
        s = sb2.param_spec(".moe.w_in", (4, 64, 32))
        assert s == P("data", None, "model"), s
        # the pod axis is excluded from both fsdp and expert fallback
        mesh3 = make_mesh((2, 2, 2), ("data", "expert", "pod"))
        sb3 = SpecBuilder(mesh3, mode="expert", pod_axis="pod", arch="x")
        for name, shape in ((".moe.w_in", (2, 64, 32)),
                            (".blocks.attn.wq", (64, 64)),
                            (".embed.w", (80, 64))):
            spec = sb3.param_spec(name, shape)
            flat = jax.tree_util.tree_leaves(tuple(spec))
            assert "pod" not in flat, (name, spec)
        print("EXPERT-SPECS-OK")
    """))
    assert "EXPERT-SPECS-OK" in out


def test_expert_specs_round_trip_shard_state_restore():
    """Engine round-trip on a ``data x expert`` mesh: every leaf that
    ``shard_state`` places must come back with the identical sharding
    from ``restore_sharding`` given its checkpoint key path — elastic
    restore cannot silently change the expert placement."""
    out = _run(textwrap.dedent("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh
        import jax.tree_util as jtu
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import lm_units
        from repro.data.synthetic import make_lm_corpus
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for
        cfg = get_config("mixtral-8x7b-smoke")
        m = build_model(cfg)
        units = lm_units(make_lm_corpus(0, 8, 10, cfg.vocab_size), 2)
        tc = TrainConfig(lr=0.2, optimizer="sgd", epochs=1,
                         pgm=PGMConfig())
        mesh = make_mesh((2, 2), ("data", "expert"))
        eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh,
                          spec_mode="expert")
        opt_init, _ = make_update_for(tc)
        p = m.init_params(jax.random.PRNGKey(0))
        o = opt_init(p)
        p, o = eng.shard_state(p, o)
        n = 0
        for tree, ck in ((p, "params"), (o, "opt")):
            for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
                got = eng.restore_sharding(
                    f"['{ck}']" + jtu.keystr(path), np.asarray(leaf))
                assert got.spec == leaf.sharding.spec, \\
                    (ck, jtu.keystr(path), got.spec, leaf.sharding.spec)
                n += 1
        assert n > 10, n
        print("EXPERT-ROUNDTRIP-OK")
    """))
    assert "EXPERT-ROUNDTRIP-OK" in out


def test_pgm_stage_b_shard_map_matches_single_device():
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import PGMConfig
        from repro.core.pgm import partitioned_gm, pgm_select_sharded
        mesh = make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        pc = PGMConfig(subset_fraction=0.25, n_partitions=8)
        ref = partitioned_gm(g, 8, 1, pc.lam, pc.eps, pc.nonneg_weights)
        got = pgm_select_sharded(mesh, "data", g, pc)
        ri = sorted(int(i) for i in ref.indices if i >= 0)
        gi = sorted(int(i) for i in got.indices if i >= 0)
        assert ri == gi, (ri, gi)
        assert int(got.n_selected) == int(ref.n_selected)
        print("PGM-SHARDMAP-OK")
    """))
    assert "PGM-SHARDMAP-OK" in out


def test_compressed_psum_modes():
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.train.compress import compressed_psum, init_error_state
        mesh = make_mesh((8,), ("pod",))
        g = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        err = init_error_state({"w": jnp.zeros((8,))})
        def f(gl):
            red, _ = compressed_psum(gl, "pod", mode="bf16")
            return red
        out = jax.shard_map(f, mesh=mesh, in_specs=({"w": P("pod")},),
                            out_specs={"w": P("pod")})(g)
        # the collective reduces AT bf16 width (cast before the pmean, so
        # the wire moves half the bytes): mean computed in bf16, then
        # upcast
        want = jnp.broadcast_to(
            g["w"].astype(jnp.bfloat16).mean(0).astype(jnp.float32), (8, 8))
        assert jnp.allclose(out["w"], want, atol=0.5), (out["w"][0], want[0])
        assert out["w"].dtype == jnp.float32
        print("PSUM-OK")
    """))
    assert "PSUM-OK" in out


@pytest.mark.parametrize("arch,policy", [
    ("minitron-8b", None),            # fsdp_sp auto
    ("mixtral-8x7b", None),           # tp/EP auto
    ("rwkv6-3b", None),               # fsdp_batch auto
])
def test_reduced_train_step_compiles_on_mesh(arch, policy):
    """Lower+compile the real train step with smoke-sized configs on a
    (2,4) mesh — fast proxy for the 512-device dry-run cells."""
    out = _run(textwrap.dedent(f"""
        import jax
        from repro.launch.mesh import make_mesh
        import repro.launch.dryrun as dr
        import repro.configs as C
        orig = C.get_config
        dr.get_config = lambda name: orig(name + "-smoke")
        import repro.launch.roofline as rf
        mesh = make_mesh((2, 4), ("data", "model"))
        fn, args = dr.build_step({arch!r}, "train_4k", mesh,
                                 policy={policy!r})
        compiled = fn.lower(*args).compile()
        assert compiled.as_text()
        print("COMPILE-OK")
    """))
    assert "COMPILE-OK" in out


def test_decode_step_compiles_on_mesh():
    out = _run(textwrap.dedent("""
        import jax
        from repro.launch.mesh import make_mesh
        import repro.launch.dryrun as dr
        import repro.configs as C
        orig = C.get_config
        dr.get_config = lambda name: orig(name + "-smoke")
        mesh = make_mesh((2, 4), ("data", "model"))
        fn, args = dr.build_step("gemma3-27b", "decode_32k", mesh)
        compiled = fn.lower(*args).compile()
        print("DECODE-COMPILE-OK")
    """))
    assert "DECODE-COMPILE-OK" in out
