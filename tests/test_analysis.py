"""The static checker's own test suite: one seeded violation per rule.

Each test plants exactly one deliberate contract violation (a lying
payload spec, a host callback in the chunk, a salt collision, a retired
import...) and asserts the checker reports the right rule ID at the right
location — plus a clean-tree smoke proving the real repo passes with zero
violations.  The registries' duplicate-name guards and the x64 launcher
guard ride along.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (RULES, Violation, apply_waivers,
                            assert_x64_disabled, audit_chunk,
                            audit_faults, audit_framed_wire,
                            audit_kernels, audit_prng, audit_registry,
                            audit_telemetry,
                            audit_wire_contracts, chunk_matrix,
                            donation_report, find_callbacks,
                            find_wide_dtypes, fingerprint, lint_source,
                            specs_equal)
from repro.analysis.contracts import harness_bundle
from repro.core.methods import get_method
from repro.core.methods.base import FSLMethod, register
from repro.core.methods.cse_fsl import CSEFSL
from repro.transport import CHANNEL_SALTS, Codec, Transport, register_codec
from repro.sched.policy import SchedulerPolicy, register_policy


@pytest.fixture(scope="module")
def bundle():
    return harness_bundle()


def _rules(violations):
    return [v.rule for v in violations]


def _patch_method(monkeypatch, name, instance):
    """Swap a registry entry for a doctored instance (restored by
    monkeypatch teardown)."""
    from repro.core.methods import base
    monkeypatch.setitem(base._REGISTRY, name, instance)


# ---------------------------------------------------------------------------
# W rules: wire contracts
# ---------------------------------------------------------------------------


def test_seeded_w001_lying_payload_specs(monkeypatch, bundle):
    class LyingSpecs(CSEFSL):
        def payload_specs(self, bundle, fsl, batch):
            up, reply = super().payload_specs(bundle, fsl, batch)
            bad = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((1,) + tuple(x.shape),
                                               x.dtype), up)
            return bad, reply

    _patch_method(monkeypatch, "cse_fsl", LyingSpecs())
    vs = audit_wire_contracts("cse_fsl", bundle=bundle)
    w = [v for v in vs if v.rule == "W001"]
    assert w and "uplink" in w[0].message
    assert "method=cse_fsl" in w[0].combo


def test_seeded_w002_lying_model_sync_specs(monkeypatch, bundle):
    class LyingSync(CSEFSL):
        def model_sync_specs(self, bundle, fsl):
            spec = super().model_sync_specs(bundle, fsl)
            leaves, treedef = jax.tree_util.tree_flatten(spec)
            leaves[0] = jax.ShapeDtypeStruct(
                tuple(leaves[0].shape) + (2,), leaves[0].dtype)
            return jax.tree_util.tree_unflatten(treedef, leaves)

    _patch_method(monkeypatch, "cse_fsl", LyingSync())
    vs = audit_wire_contracts("cse_fsl", bundle=bundle)
    assert "W002" in _rules(vs)


def test_seeded_w003_wrong_wire_channels(monkeypatch, bundle):
    class WrongChannels(CSEFSL):
        wire_channels = ("uplink", "downlink")   # CSE-FSL is non-blocking

    _patch_method(monkeypatch, "cse_fsl", WrongChannels())
    vs = audit_wire_contracts("cse_fsl", bundle=bundle)
    w = [v for v in vs if v.rule == "W003"]
    assert w and "downlink" in w[0].message


# ---------------------------------------------------------------------------
# C rules: compiled-chunk hygiene
# ---------------------------------------------------------------------------


def test_seeded_c001_host_callback_in_chunk(monkeypatch, bundle):
    class CallbackChunk(CSEFSL):
        def make_chunk_step(self, *a, **kw):
            real = super().make_chunk_step(*a, **kw)

            def chunk(state, batches, lrs):
                jax.debug.print("round {r}", r=state["round"])
                return real(state, batches, lrs)
            return chunk

    _patch_method(monkeypatch, "cse_fsl", CallbackChunk())
    vs, _ = audit_chunk("cse_fsl", bundle=bundle)
    c = [v for v in vs if v.rule == "C001"]
    assert c and "debug_print" in c[0].message
    assert "method=cse_fsl" in c[0].combo


def test_seeded_c001_on_kernel_audit_surface(monkeypatch):
    from repro.kernels import ops

    def bad_surface():
        def leaky(x):
            jax.debug.print("x {x}", x=x)
            return x * 2.0
        return (("leaky", leaky,
                 (jax.ShapeDtypeStruct((4,), jnp.float32),)),)

    monkeypatch.setattr(ops, "audit_specs", bad_surface)
    vs = audit_kernels()
    assert _rules(vs) == ["C001"] and vs[0].combo == "kernel=leaky"


def test_kernel_audit_surface_is_clean():
    assert audit_kernels() == []


def test_seeded_c002_float64_leak():
    from repro.analysis.contracts import _hygiene
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: jnp.sum(x.astype(jnp.float64)))(
                jax.ShapeDtypeStruct((4,), jnp.float32))
        vs = _hygiene(jaxpr, "seeded")
    c = [v for v in vs if v.rule == "C002"]
    assert c and "float64" in c[0].message
    assert find_wide_dtypes(jaxpr)


# ---------------------------------------------------------------------------
# D001: donation
# ---------------------------------------------------------------------------


def test_seeded_d001_carry_shape_drift(monkeypatch, bundle):
    class DriftingCarry(CSEFSL):
        def make_chunk_step(self, *a, **kw):
            real = super().make_chunk_step(*a, **kw)

            def chunk(state, batches, lrs):
                state, metrics, mask = real(state, batches, lrs)
                state = dict(state)
                state["round"] = state["round"].astype(jnp.float32)
                return state, metrics, mask
            return chunk

    _patch_method(monkeypatch, "cse_fsl", DriftingCarry())
    vs, _ = audit_chunk("cse_fsl", bundle=bundle)
    d = [v for v in vs if v.rule == "D001"]
    assert d and "donation-compatible" in d[0].message


def test_donation_report_counts_unusable_donation():
    spec = jax.ShapeDtypeStruct((8,), jnp.float32)
    aliased, donatable, dropped = donation_report(
        lambda x: jnp.sum(x), (spec,))
    assert donatable == 1 and aliased == 0
    aliased, donatable, _ = donation_report(lambda x: x * 2.0, (spec,))
    assert aliased == donatable == 1


# ---------------------------------------------------------------------------
# P001: PRNG streams
# ---------------------------------------------------------------------------


def test_seeded_p001_salt_ignoring_transport():
    class SaltBlind(Transport):
        def unit_key(self, unit, client=None, salt: int = 0):
            return super().unit_key(unit, client=client, salt=0)

    vs = audit_prng(transport=SaltBlind())
    p = [v for v in vs if v.rule == "P001"]
    assert p and "collision" in p[0].message


def test_channel_salts_are_the_contract():
    assert set(CHANNEL_SALTS) == {"uplink", "downlink", "model_up",
                                  "model_down"}
    assert len(set(CHANNEL_SALTS.values())) == 4
    assert audit_prng() == []


# ---------------------------------------------------------------------------
# F001: fault-injection stream discipline + framed wire transparency
# ---------------------------------------------------------------------------


def test_seeded_f001_retry_fold_collision(monkeypatch):
    # RETRY_FOLD = 0 lands the retry stream exactly on the uplink
    # channel's unit-0 fold (unit * 2 + salt with salt=0): the checker
    # must catch the coupling before any fault run draws corrupted bits
    # from a codec's rounding stream
    import repro.faults.model as fmod
    monkeypatch.setattr(fmod, "RETRY_FOLD", 0)
    vs = audit_faults()
    f = [v for v in vs if v.rule == "F001"]
    assert f and "collides with a codec stream" in f[0].message
    assert f[0].combo == "faults"


def test_seeded_f001_internal_retry_collision(monkeypatch):
    from repro.faults import retry_key as real_retry

    def folded_retry(transport, unit, client=None):
        return real_retry(transport, unit % 2, client=client)

    import repro.faults.model as fmod
    monkeypatch.setattr(fmod, "retry_key", folded_retry)
    import repro.faults as fpkg
    monkeypatch.setattr(fpkg, "retry_key", folded_retry)
    vs = audit_faults()
    f = [v for v in vs if v.rule == "F001"]
    assert f and "between units" in f[0].message


def test_audit_faults_clean():
    assert audit_faults() == []


def test_seeded_w001_framed_sweep(monkeypatch, bundle):
    class LyingSpecs(CSEFSL):
        def payload_specs(self, bundle, fsl, batch):
            up, reply = super().payload_specs(bundle, fsl, batch)
            bad = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((1,) + tuple(x.shape),
                                               x.dtype), up)
            return bad, reply

    _patch_method(monkeypatch, "cse_fsl", LyingSpecs())
    vs = audit_framed_wire("cse_fsl", bundle=bundle)
    w = [v for v in vs if v.rule == "W001"]
    assert w and "framed" in w[0].message
    assert "framed=True" in w[0].combo


# ---------------------------------------------------------------------------
# R001: recompilation guard
# ---------------------------------------------------------------------------


def test_seeded_r001_construction_varying_chunk(monkeypatch, bundle):
    class Flaky(CSEFSL):
        builds = 0

        def make_chunk_step(self, *a, **kw):
            real = super().make_chunk_step(*a, **kw)
            type(self).builds += 1
            if type(self).builds == 1:
                return real

            def chunk(state, batches, lrs):      # structurally different
                state, metrics, mask = real(state, batches, lrs)
                metrics = {k: v + 0.0 for k, v in metrics.items()}
                return state, metrics, mask
            return chunk

    _patch_method(monkeypatch, "cse_fsl", Flaky())
    vs, _ = audit_chunk("cse_fsl", bundle=bundle)
    r = [v for v in vs if v.rule == "R001"]
    assert r and "fingerprint" in r[0].message


def test_trainer_chunk_fingerprint_stable(bundle):
    import numpy as np
    from repro.configs.base import FSLConfig
    from repro.core.trainer import Trainer
    fsl = FSLConfig(num_clients=2, h=2, method="cse_fsl")
    batch = (np.zeros((2, 2, 2, 8, 8, 1), np.float32),
             np.zeros((2, 2, 2), np.int32))
    a, b = (Trainer(bundle, fsl).chunk_fingerprint(batch, chunk=2)
            for _ in range(2))
    assert a == b and len(a) == 64


def test_fingerprint_is_structural():
    spec = jax.ShapeDtypeStruct((4,), jnp.float32)
    assert fingerprint(lambda x: x * 2.0, spec) == \
        fingerprint(lambda y: y * 2.0, spec)
    assert fingerprint(lambda x: x * 2.0, spec) != \
        fingerprint(lambda x: x * 3.0, spec)


# ---------------------------------------------------------------------------
# T001: telemetry neutrality
# ---------------------------------------------------------------------------


def test_seeded_t001_callback_in_telemetry_chunk(bundle):
    """A telemetry implementation that reaches into the scan body (the
    classic host-callback shortcut) must fire BOTH halves of the jaxpr
    audit: the callback detector and the on/off fingerprint diff."""
    from repro.analysis.contracts import harness_fsl

    m = get_method("cse_fsl")
    inner = m.make_chunk_step(harness_bundle(), harness_fsl("cse_fsl"))

    def evil_chunk(state, batches, lrs):
        jax.debug.print("round lr {r}", r=lrs[0])   # in-scan emission
        return inner(state, batches, lrs)

    vs = audit_telemetry(bundle=bundle, telemetry_chunk=evil_chunk)
    assert _rules(vs) == ["T001", "T001"]
    assert any("debug_print" in v.message for v in vs)
    assert any("changed the compiled program" in v.message for v in vs)
    assert all("program=telemetry" in v.combo for v in vs)


def test_seeded_t001_telemetry_in_traced_code():
    """The AST half: method/kernel code may neither import the telemetry
    package nor poke a ``.telemetry`` attribute — the same source is fine
    in engine files (that is exactly where emission lives)."""
    src = ("from repro.telemetry import Telemetry\n"
           "def f(self, x):\n"
           "    self.telemetry.counter('steps')\n"
           "    return x\n")
    vs = lint_source(src, "src/repro/core/methods/evil.py",
                     traced_scope=True)
    assert _rules(vs) == ["T001", "T001"]
    assert {v.line for v in vs} == {1, 3}
    assert lint_source(src, "src/repro/core/trainer.py",
                       traced_scope=False) == []
    # the dynamic-import escape hatch is closed too
    vs = lint_source("import importlib\n"
                     "t = importlib.import_module('repro.telemetry')\n",
                     "src/repro/kernels/evil.py", traced_scope=True)
    assert _rules(vs) == ["T001"] and vs[0].line == 2


def test_t001_clean_on_real_tree(bundle):
    """Both halves pass on the actual repo: the chunk programs are
    telemetry-blind and no traced file touches the recorder."""
    assert audit_telemetry(bundle=bundle, methods=("cse_fsl",)) == []


# ---------------------------------------------------------------------------
# A rules: AST / registry lint
# ---------------------------------------------------------------------------


def test_seeded_a001_retired_shim_import():
    src = ("import numpy as np\n"
           "from repro.core.protocol import init_state\n")
    vs = lint_source(src, "fake.py")
    assert _rules(vs) == ["A001"]
    assert vs[0].line == 2 and vs[0].file == "fake.py"

    vs = lint_source("import importlib\n"
                     "m = importlib.import_module('repro.core.baselines')\n",
                     "fake.py")
    assert _rules(vs) == ["A001"] and vs[0].line == 2


def test_seeded_a002_traced_python_branch():
    src = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    if jnp.sum(x) > 0:\n"
           "        return x\n"
           "    return -x\n")
    vs = lint_source(src, "core/methods/fake.py", traced_scope=True)
    a = [v for v in vs if v.rule == "A002"]
    assert a and a[0].line == 3 and "jnp.sum" in a[0].message
    # same file outside the traced scope: host-side branching is fine
    assert lint_source(src, "trainer.py", traced_scope=False) == []


def test_a002_inline_waiver_and_static_attrs():
    src = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    if jnp.sum(x) > 0:  # analysis: waive=A002\n"
           "        return x\n"
           "    y = x if jnp.issubdtype(x.dtype, jnp.floating) else x\n"
           "    return y\n")
    assert lint_source(src, "core/methods/fake.py", traced_scope=True) == []


def test_seeded_a003_incomplete_method_stub(bundle):
    class Stub(FSLMethod):
        name = "stub"

    vs = audit_registry(methods={"stub": Stub()}, bundle=bundle)
    a = [v for v in vs if v.rule == "A003"]
    assert a and "make_async_hooks" in a[0].message
    assert a[0].file and a[0].file.endswith("test_analysis.py")
    assert a[0].line is not None


def test_seeded_a003_inconsistent_channels(bundle):
    class BadChannels(CSEFSL):
        name = "cse_fsl"
        wire_channels = ("uplink", "downlink")   # vs downloads_gradients

    vs = audit_registry(methods={"cse_fsl": BadChannels()}, bundle=bundle)
    a = [v for v in vs if v.rule == "A003"]
    assert a and "contradict" in a[0].message


# ---------------------------------------------------------------------------
# Registries: duplicate names are an error, never a silent overwrite
# ---------------------------------------------------------------------------


def test_duplicate_method_registration_raises():
    with pytest.raises(ValueError, match="duplicate FSL method"):
        @register
        class Dup(FSLMethod):          # noqa: F811 — the point
            name = "cse_fsl"
    assert type(get_method("cse_fsl")) is CSEFSL    # registry untouched


def test_duplicate_codec_registration_raises():
    with pytest.raises(ValueError, match="duplicate codec"):
        @register_codec
        class DupCodec(Codec):
            name = "int8"


def test_duplicate_policy_registration_raises():
    with pytest.raises(ValueError, match="duplicate policy"):
        @register_policy
        class DupPolicy(SchedulerPolicy):
            name = "wait_all"


# ---------------------------------------------------------------------------
# The x64 launcher guard
# ---------------------------------------------------------------------------


def test_x64_guard():
    assert_x64_disabled()                        # default config: fine
    jax.config.update("jax_enable_x64", True)
    try:
        with pytest.raises(SystemExit, match="float64 is globally enabled"):
            assert_x64_disabled(where="test")
    finally:
        jax.config.update("jax_enable_x64", False)
    assert_x64_disabled()


# ---------------------------------------------------------------------------
# Rule plumbing + the clean tree
# ---------------------------------------------------------------------------


def test_waivers_mark_but_keep_violations():
    vs = [Violation("A002", "x", file="f.py", line=3),
          Violation("C001", "y", combo="method=m")]
    out = apply_waivers(vs, {"A002"})
    assert [v.waived for v in out] == [True, False]
    assert "[waived]" in str(out[0]) and "f.py:3" in str(out[0])
    assert "method=m" in out[1].where()


def test_rule_catalogue_covers_all_emitted_rules():
    assert set(RULES) == {"W001", "W002", "W003", "C001", "C002", "D001",
                          "P001", "F001", "R001", "T001", "A001", "A002",
                          "A003"}


def test_specs_equal_reports_first_mismatch():
    a = {"x": jax.ShapeDtypeStruct((2, 3), jnp.float32)}
    b = {"x": jax.ShapeDtypeStruct((2, 3), jnp.float16)}
    assert specs_equal(a, a) is None
    assert "float16" in specs_equal(a, b)


def test_chunk_matrix_shapes():
    fast, full = chunk_matrix(False), chunk_matrix(True)
    assert len(full) > len(fast)
    assert any(c.server_update == "batched" for c in full)
    assert all(c.server_update == "sequential" for c in fast)


def test_clean_tree_has_zero_violations(bundle):
    """The real repo passes its own checker (fast mode): this is the
    in-suite mirror of CI's ``python -m repro.analysis.check``."""
    from repro.analysis.ast_lint import lint_paths
    from repro.core.methods import available_methods
    vs = []
    vs += audit_prng()
    vs += audit_faults()
    vs += audit_registry(bundle=bundle)
    vs += audit_telemetry(bundle=bundle)
    vs += audit_kernels()
    for nm in available_methods():
        vs += audit_wire_contracts(nm, bundle=bundle)
        vs += audit_framed_wire(nm, bundle=bundle)
    # one representative coded chunk per blocking/non-blocking shape
    for combo in (("cse_fsl", "int8", True), ("fsl_mc", "int8", False)):
        cv, fp = audit_chunk(combo[0], combo[1], masked=combo[2],
                             bundle=bundle)
        vs += cv
        assert len(fp) == 64
    vs += lint_paths()
    assert vs == [], "\n".join(map(str, vs))
