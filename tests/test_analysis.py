"""The static-contract checker checks itself (repro.analysis):

* level 1 — every lint rule fires on a minimal violation fixture and
  stays silent on the matching clean fixture; suppressions are honored
  (and bare/unknown suppressions are themselves findings); the JSON
  output schema is stable; and the full rule set runs clean on the
  repo's own ``src/`` tree (the ``make check-static`` gate);
* level 2 — the ``analysis.contracts`` checkers prove and refute:
  ``track_compiles``/``assert_retrace_free`` count real XLA compiles,
  ``assert_donated`` reads the aliasing/donor marks, the host-transfer
  checkers catch callbacks (statically) and implicit fetches (at
  runtime), and the replica-group parser handles both compiled HLO
  encodings — plus the scan engine's own epoch executable satisfies
  donation + residency.
"""
import json
import textwrap
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts
from repro.analysis.lint import (JSON_SCHEMA_VERSION, all_rules, main,
                                 run_lint, to_json)

pytestmark = pytest.mark.analysis

REPO_ROOT = Path(__file__).resolve().parent.parent


def _lint(tmp_path, code, rel="src/repro/train/mod.py", rules=None):
    """Lint one dedented snippet placed at ``rel`` under a tmp root."""
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    reg = all_rules()
    sel = {n: reg[n] for n in rules} if rules is not None else reg
    return run_lint(tmp_path, rules=sel, files=[p])


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# per-rule violation / clean fixture pairs
# ---------------------------------------------------------------------------

def test_host_sync_jit_fires_and_clean(tmp_path):
    bad = """
        import jax

        @jax.jit
        def step(x):
            return x * float(x.mean())
    """
    clean = """
        import jax

        @jax.jit
        def step(x):
            scale = float(len(x.shape))     # shape math is static
            return x * scale
    """
    assert _rules_of(_lint(tmp_path, bad, rules=["host-sync-jit"])) == \
        {"host-sync-jit"}
    assert _lint(tmp_path, clean, rules=["host-sync-jit"]) == []


def test_host_sync_jit_sees_scan_bodies_transitively(tmp_path):
    bad = """
        import jax

        def body(carry, x):
            return carry + x, bool(x.sum())

        def epoch(xs):
            return jax.lax.scan(body, 0.0, xs)
    """
    found = _lint(tmp_path, bad, rules=["host-sync-jit"])
    assert _rules_of(found) == {"host-sync-jit"}


def test_host_sync_loop_catches_per_slot_eviction_fetch(tmp_path):
    # regression for the SlotEngine eviction sweep this PR fixed: a
    # device fetch per finished slot inside the host loop
    bad = """
        import numpy as np

        def sweep(state, finished, n_out):
            outs = []
            for slot in finished:
                toks = np.asarray(state["out"][slot])[: int(n_out[slot])]
                outs.append(toks)
            return outs
    """
    clean = """
        import numpy as np

        def sweep(state, finished, n_out):
            out_pool = np.asarray(state["out"])
            counts = np.asarray(n_out)
            outs = []
            for slot in finished:
                outs.append(out_pool[slot][: int(counts[slot])])
            return outs
    """
    rel = "src/repro/serve/mod.py"
    assert "host-sync-loop" in _rules_of(
        _lint(tmp_path, bad, rel=rel, rules=["host-sync-loop"]))
    assert _lint(tmp_path, clean, rel=rel, rules=["host-sync-loop"]) == []


def test_key_reuse_fires_and_fold_in_is_sanctioned(tmp_path):
    bad = """
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.normal(key, (3,))
            return a + b
    """
    loop_bad = """
        import jax

        def sample(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, (3,)))
            return out
    """
    clean = """
        import jax

        def sample(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(jax.random.fold_in(key, i),
                                             (3,)))
            return out
    """
    assert _rules_of(_lint(tmp_path, bad, rules=["key-reuse"])) == \
        {"key-reuse"}
    assert _rules_of(_lint(tmp_path, loop_bad, rules=["key-reuse"])) == \
        {"key-reuse"}
    assert _lint(tmp_path, clean, rules=["key-reuse"]) == []


def test_dtype_widen_fires_and_clean(tmp_path):
    bad = """
        import jax.numpy as jnp

        def widen(x):
            return x.astype("float64") + jnp.zeros(3, dtype=jnp.float64)
    """
    clean = """
        import jax.numpy as jnp
        import numpy as np

        def narrow(x):
            host = np.float64(0.5)          # host-side f64 is fine
            return x.astype(jnp.bfloat16) * jnp.float32(host)
    """
    found = _lint(tmp_path, bad, rules=["dtype-widen"])
    assert _rules_of(found) == {"dtype-widen"} and len(found) == 2
    assert _lint(tmp_path, clean, rules=["dtype-widen"]) == []


def test_collective_cast_order_fires_and_clean(tmp_path):
    bad = """
        import jax, jax.numpy as jnp

        def reduce(g):
            return jax.lax.pmean(g, "pod").astype(jnp.bfloat16)
    """
    clean = """
        import jax
        import jax.numpy as jnp

        def reduce(g):
            r = jax.lax.pmean(g.astype(jnp.bfloat16), "pod")
            return r.astype(jnp.float32)    # widening back is fine
    """
    assert _rules_of(_lint(tmp_path, bad,
                           rules=["collective-cast-order"])) == \
        {"collective-cast-order"}
    assert _lint(tmp_path, clean, rules=["collective-cast-order"]) == []


def test_pallas_blockspec_fires_and_clean(tmp_path):
    bad = """
        import jax.experimental.pallas as pl

        def op(x, block):
            scale = x * 2
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i * scale,))],
                interpret=False)(x)
    """
    clean = """
        import jax.experimental.pallas as pl

        def op(x, block):
            n = x.shape[0] // block         # shape math: static
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8,), lambda i: (i * n,))],
                interpret=False)(x)
    """
    rel = "src/repro/kernels/toy/kernel.py"
    assert _rules_of(_lint(tmp_path, bad, rel=rel,
                           rules=["pallas-blockspec"])) == \
        {"pallas-blockspec"}
    assert _lint(tmp_path, clean, rel=rel, rules=["pallas-blockspec"]) == []


def test_pallas_interpret_fires_and_clean(tmp_path):
    bad_kernel = """
        import jax.experimental.pallas as pl

        def run(x):
            return pl.pallas_call(kern, grid=(4,))(x)
    """
    bad_ops = """
        def toy_op(x):
            return _pallas_toy(x)
    """
    clean_ops = """
        def toy_op(x, interpret=False):
            return _pallas_toy(x, interpret=interpret)
    """
    assert _rules_of(_lint(tmp_path, bad_kernel,
                           rel="src/repro/kernels/toy/kernel.py",
                           rules=["pallas-interpret"])) == \
        {"pallas-interpret"}
    found = _lint(tmp_path, bad_ops, rel="src/repro/kernels/toy/ops.py",
                  rules=["pallas-interpret"])
    assert len(found) == 2                  # missing param + dropped kwarg
    assert _lint(tmp_path, clean_ops,
                 rel="src/repro/kernels/toy/ops.py",
                 rules=["pallas-interpret"]) == []


def test_bench_docs_drift_fires_and_clean(tmp_path):
    (tmp_path / "benchmarks").mkdir(parents=True)
    (tmp_path / "benchmarks" / "bench_toy.py").write_text(
        'OUT = "BENCH_toy.json"\nKEYS = ["toy_steps_per_s"]\n')
    readme = tmp_path / "README.md"
    reg = all_rules()
    rule = reg["bench-docs-drift"]

    readme.write_text("`BENCH_toy.json` reports `bogus_steps_per_s`.\n")
    found = rule.check(tmp_path)
    assert found and all(f.rule == "bench-docs-drift" for f in found)

    readme.write_text("`BENCH_toy.json` reports `toy_steps_per_s`.\n")
    assert rule.check(tmp_path) == []


# ---------------------------------------------------------------------------
# suppression + hygiene + schema + self-check
# ---------------------------------------------------------------------------

def test_suppression_is_honored_and_hygiene_enforced(tmp_path):
    suppressed = """
        import jax

        @jax.jit
        def step(x):
            return x * float(x.mean())  # repro: noqa[host-sync-jit] -- fixture: deliberate
    """
    bare = """
        import jax

        @jax.jit
        def step(x):
            return x * float(x.mean())  # repro: noqa[host-sync-jit]
    """
    unknown = """
        x = 1  # repro: noqa[no-such-rule] -- why
    """
    assert _lint(tmp_path, suppressed,
                 rules=["host-sync-jit", "noqa-hygiene"]) == []
    found = _lint(tmp_path, bare, rules=["host-sync-jit", "noqa-hygiene"])
    # the finding is hidden but the bare suppression is itself flagged
    assert _rules_of(found) == {"noqa-hygiene"}
    assert "justification" in found[0].message
    found = _lint(tmp_path, unknown, rules=["noqa-hygiene"])
    assert any("unknown rule" in f.message for f in found)


def test_docstring_mention_of_noqa_is_not_a_suppression(tmp_path):
    code = '''
        def helper():
            """Suppression syntax is `# repro: noqa[rule]`."""
            return 1
    '''
    assert _lint(tmp_path, code, rules=["noqa-hygiene"]) == []


def test_json_schema_is_stable(tmp_path):
    bad = """
        import jax

        @jax.jit
        def step(x):
            return float(x.mean())
    """
    reg = all_rules()
    findings = _lint(tmp_path, bad, rules=["host-sync-jit"])
    blob = json.loads(json.dumps(to_json(
        findings, {"host-sync-jit": reg["host-sync-jit"]})))
    assert set(blob) == {"version", "rules", "findings", "counts"}
    assert blob["version"] == JSON_SCHEMA_VERSION
    assert blob["rules"] == ["host-sync-jit"]
    assert blob["counts"] == {"host-sync-jit": 1}
    (f,) = blob["findings"]
    assert set(f) == {"rule", "path", "line", "message"}
    assert f["path"].endswith("mod.py") and f["line"] > 1


def test_rule_set_runs_clean_on_own_src():
    """The ``make check-static`` gate: zero findings on the repo, with
    the full registry (>= 8 rules) active."""
    rules = all_rules()
    assert len(rules) >= 8
    findings = run_lint(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_list_and_exit_codes(tmp_path, capsys):
    assert main(["--list"]) == 0
    names = capsys.readouterr().out
    assert "host-sync-jit:" in names and "noqa-hygiene:" in names
    with pytest.raises(SystemExit):
        main(["--rule", "no-such-rule"])


# ---------------------------------------------------------------------------
# level 2: contracts
# ---------------------------------------------------------------------------

def test_track_compiles_counts_and_retrace_free_raises():
    @jax.jit
    def f(x):
        return x * 3 + 1

    x = jnp.ones(7)
    with contracts.track_compiles() as log:
        f(x).block_until_ready()
    assert log.count >= 1 and any("f" in n for n in log.names)
    with contracts.assert_retrace_free("warm f"):
        f(x).block_until_ready()

    @jax.jit
    def g(x):
        return x - 2

    with pytest.raises(AssertionError, match="retraced"):
        with contracts.assert_retrace_free("cold g"):
            g(x).block_until_ready()


def test_assert_donated_positive_negative_and_skip():
    def f(carry, x):
        return carry + x, carry * x

    donating = jax.jit(f, donate_argnums=(0,)).lower(jnp.ones(3),
                                                     jnp.ones(3))
    contracts.assert_donated(donating, jnp.ones(3))
    with pytest.raises(AssertionError, match="not donated"):
        contracts.assert_donated(donating, (jnp.ones(3), jnp.ones(3)))
    # skip= checks donation *after* a non-donated prefix
    tail = jax.jit(f, donate_argnums=(1,)).lower(jnp.ones(3), jnp.ones(3))
    contracts.assert_donated(tail, jnp.ones(3), skip=jnp.ones(3))
    with pytest.raises(AssertionError, match="not donated"):
        contracts.assert_donated(tail, jnp.ones(3))


def test_assert_no_host_transfers_flags_callbacks():
    def cb(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    low = jax.jit(cb).lower(jnp.ones(3))
    with pytest.raises(AssertionError, match="host transfer"):
        contracts.assert_no_host_transfers(low)

    def pure(x):
        return x * 2

    plow = jax.jit(pure).lower(jnp.ones(3))
    contracts.assert_no_host_transfers(plow, plow.compile().as_text())


def test_no_implicit_transfers_guard():
    x = jnp.arange(4.0)
    with contracts.no_implicit_transfers():
        (x * 2).block_until_ready()         # dispatch alone is fine
    if jax.default_backend() == "cpu":
        # CPU arrays live in host memory; no D2H copy ever routes
        # through the guard (see the helper's docstring)
        pytest.skip("transfer guard is vacuous on the CPU backend")
    with pytest.raises(Exception, match="[Dd]isallow"):
        with contracts.no_implicit_transfers():
            np.asarray(x * 2)


def test_replica_group_parser_handles_both_encodings():
    lit = "all-reduce(...), replica_groups={{0,2},{1,3}}, to_apply=%x"
    assert contracts.parse_replica_groups(lit) == [[0, 2], [1, 3]]
    iota = "all-reduce(...), replica_groups=[2,2]<=[2,2]T(1,0), foo"
    assert contracts.parse_replica_groups(iota) == [[0, 2], [1, 3]]
    flat = "all-reduce(...), replica_groups=[1,4]<=[4], foo"
    assert contracts.parse_replica_groups(flat) == [[0, 1, 2, 3]]
    assert contracts.parse_replica_groups("all-reduce, no groups") is None


def test_expected_groups_from_mesh_axes():
    dev = np.array([[types.SimpleNamespace(id=0),
                     types.SimpleNamespace(id=1)],
                    [types.SimpleNamespace(id=2),
                     types.SimpleNamespace(id=3)]])
    mesh = types.SimpleNamespace(devices=dev, axis_names=("data", "pod"))
    assert contracts.expected_groups(mesh, "pod") == [[0, 1], [2, 3]]
    assert contracts.expected_groups(mesh, "data") == [[0, 2], [1, 3]]
    text = ("%ar = f32[2]{0} all-reduce(%z), channel_id=1, "
            "replica_groups={{0,1},{2,3}}, to_apply=%sum")
    contracts.assert_replica_groups(text, mesh, "pod")
    with pytest.raises(AssertionError, match="no all-reduce grouped"):
        contracts.assert_replica_groups(text, mesh, "data")


def test_scan_engine_epoch_executable_satisfies_contracts():
    """The single-device scan engine's epoch executable: (params, opt)
    carry donated, body device-resident — the fast-tier leg of the
    contract matrix (the pod/sharded legs live in the slow 4-device
    tests, the serving leg in tests/test_serve_engine.py)."""
    from repro.configs import get_config
    from repro.configs.base import PGMConfig, TrainConfig
    from repro.data.pipeline import lm_units
    from repro.data.synthetic import make_lm_corpus
    from repro.models.api import build_model
    from repro.train.engine import EpochEngine
    from repro.train.optim import make_update_for

    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 8, 10, cfg.vocab_size), 4)
    tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=1, pgm=PGMConfig())
    eng = EpochEngine(m, tc, units, batch_units=2)
    opt_init, _ = make_update_for(tc)
    p = m.init_params(jax.random.PRNGKey(0))
    o = opt_init(p)
    low = eng.lower_epoch(p, o, 0.5, eng.full_plan(0))
    contracts.assert_donated(low, (p, o))
    contracts.assert_no_host_transfers(low, low.compile().as_text())
