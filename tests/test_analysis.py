"""Tests for the in-repo static analysis (`repro.analysis`).

Three layers:

1. fixture trees / fixture programs — one deliberately-violating snippet
   per rule, asserting the pass reports exactly that rule at that site
   (and that the pragma / baseline escape hatches behave);
2. the clean-tree gate — all four passes over the real ``src/repro`` with
   the checked-in baseline must report zero active findings (the same
   invariant CI enforces via ``python -m repro.analysis --all``);
3. regression tests for the concurrency fixes the lock pass drove
   (handoff counter atomicity, AsyncEngine loop-owned mirrors, prefill
   pool thread deprioritization hardening).
"""
import asyncio
import os
import textwrap
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import default_baseline, default_root, run_passes
from repro.analysis.common import (
    Finding, load_baseline, parse_pragmas, split_baselined)
from repro.analysis import determinism, locklint

REPO_SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def _tree(tmp_path: Path, files: dict) -> Path:
    root = tmp_path / "fixture"
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return root


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------- lock pass --

LOCK_FIXTURE = """\
    import threading

    class Chan:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0  # guarded-by: self._lock
            self.items = []  # owned-by: worker

        def bad_unguarded(self):
            self.count += 1

        def good_guarded(self):
            with self._lock:
                self.count += 1

        def good_thread(self):  # thread: worker
            self.items.append(1)

        def good_nested(self):  # thread: worker
            def inner():
                self.items.append(2)
            return inner

        def bad_thread(self):
            self.items.append(3)
"""


def test_lock_unguarded_and_wrong_thread(tmp_path):
    root = _tree(tmp_path, {"mod.py": LOCK_FIXTURE})
    found = locklint.run(root)
    assert _rules(found) == ["lock:thread", "lock:unguarded"]
    by_rule = {f.rule: f for f in found}
    assert "bad_unguarded" in by_rule["lock:unguarded"].message
    assert "bad_thread" in by_rule["lock:thread"].message
    # findings carry a usable location
    assert by_rule["lock:unguarded"].path == "mod.py"
    assert by_rule["lock:unguarded"].line > 0


def test_lock_init_exempt_and_annotation_collection(tmp_path):
    root = _tree(tmp_path, {"mod.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: self._lock
                self.n = 1  # __init__ writes are exempt: not shared yet
    """})
    assert locklint.run(root) == []


def test_lock_pragma_waives_line_and_def(tmp_path):
    root = _tree(tmp_path, {"mod.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: self._lock

            def waived_line(self):
                return self.n  # analysis: allow(lock:unguarded) — torn read tolerated

            def waived_def(self):  # analysis: allow(lock:unguarded) — whole body audited
                self.n += 1
                return self.n

            def still_bad(self):
                return self.n
    """})
    found = locklint.run(root)
    assert _rules(found) == ["lock:unguarded"]
    assert "still_bad" in found[0].message


def test_lock_cross_object_bind(tmp_path):
    root = _tree(tmp_path, {
        "pool.py": """\
            class Pool:
                def __init__(self):
                    self.state = None  # owned-by: pool-thread
        """,
        "user.py": """\
            # analysis: bind(pool=Pool)

            def misuse(pool):
                pool.state = 3

            def fine(pool):  # thread: pool-thread
                pool.state = 4
        """,
    })
    found = locklint.run(root)
    assert _rules(found) == ["lock:thread"]
    assert found[0].path == "user.py"
    assert "Pool.state" in found[0].message


def test_lock_shared_global_rebind(tmp_path):
    root = _tree(tmp_path, {
        "sing.py": """\
            class T:
                pass

            # analysis: shared-global(TRACER)
            TRACER = T()
        """,
        "evil.py": """\
            from fixture import sing

            def swap():
                sing.TRACER = None
        """,
    })
    found = locklint.run(root)
    assert _rules(found) == ["lock:global-rebind"]
    assert found[0].path == "evil.py"


def test_pragma_without_reason_is_a_finding(tmp_path):
    root = _tree(tmp_path, {"mod.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: self._lock

            def f(self):
                return self.n  # analysis: allow(lock:unguarded)
    """})
    found = locklint.run(root)
    # the reasonless pragma is itself flagged AND does not waive the rule
    assert _rules(found) == ["analysis:pragma-no-reason", "lock:unguarded"]


def test_comment_block_pragma_covers_following_def():
    waivers_line, waivers_def, findings = parse_pragmas(textwrap.dedent("""\
        # analysis: allow(lock:unguarded) — two-line justification that
        # wraps onto a continuation comment line
        def target(self):
            return self.n
    """), "mod.py")
    assert findings == []
    assert waivers_def == {3: {"lock:unguarded"}}


# -------------------------------------------------------- determinism pass --

def test_det_wallclock_flagged_and_pragma_waived(tmp_path):
    root = _tree(tmp_path, {"sched.py": """\
        import time

        def decide(queue):
            return time.time() < queue[0].deadline

        def metered(stats):
            stats.t = time.perf_counter()  # analysis: allow(det:wallclock) — stats only
    """})
    found = determinism.run(root)
    assert _rules(found) == ["det:wallclock"]
    assert "decide" in found[0].message


def test_det_bare_set_iteration(tmp_path):
    root = _tree(tmp_path, {"sched.py": """\
        def order(slots):
            live = {s for s in slots if s.busy}
            out = []
            for s in live:
                out.append(s)
            return out

        def fine(slots):
            live = {s for s in slots if s.busy}
            return [s for s in sorted(live)]
    """})
    found = determinism.run(root)
    assert _rules(found) == ["det:bare-set-iter"]
    assert "order" in found[0].message


def test_det_unkeyed_prng(tmp_path):
    root = _tree(tmp_path, {"samp.py": """\
        import jax

        def bad(logits, seed):
            return jax.random.categorical(jax.random.PRNGKey(seed), logits)

        def good(logits, key, step):
            k = jax.random.fold_in(key, step)
            return jax.random.categorical(k, logits)

        def also_good(logits, key):
            return jax.random.categorical(jax.random.split(key)[0], logits)
    """})
    found = determinism.run(root)
    assert _rules(found) == ["det:unkeyed-prng"]
    assert "bad" in found[0].message


# ------------------------------------------------------------- kernel pass --

def _bad_kernel_ops():
    """Deliberately-broken fake ops exercised through check_op: the checker
    must catch each invariant violation with no real kernel executing."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def oob_index_map(x):  # index map walks past the operand
        n = x.shape[0]
        return pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(n // 8,),
            in_specs=[pl.BlockSpec((8,), lambda i: (i + 1,))],
            out_specs=pl.BlockSpec((8,), lambda i: (i,)),
            out_shape=jnp.zeros((n,), jnp.float32),
        )(x)

    def bad_divisibility(x):  # block does not divide the (unpadded) dim
        n = x.shape[0]
        return pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(1,),
            in_specs=[pl.BlockSpec((7,), lambda i: (0,))],
            out_specs=pl.BlockSpec((n,), lambda i: (0,)),
            out_shape=jnp.zeros((n,), jnp.float32),
        )(x)

    def fp_materializing_quant(k_q, k_scale):  # dequantizes the WHOLE cache
        deq = k_q.astype(jnp.float32) * k_scale[..., None]
        return pl.pallas_call(
            lambda k_ref, o_ref: None,
            grid=(1,),
            in_specs=[pl.BlockSpec(deq.shape, lambda i: (0,) * deq.ndim)],
            out_specs=pl.BlockSpec((1,), lambda i: (0,)),
            out_shape=jnp.zeros((1,), jnp.float32),
        )(deq)

    def clean(x):
        n = x.shape[0]
        return pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(n // 8,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jnp.zeros((n, 128), jnp.float32),
        )(x)

    def bad_tiling(scale):  # (1, 1, bk) scale rows of a (B, Hkv, S) plane
        b, hkv, s = scale.shape
        bk = 128
        return pl.pallas_call(
            lambda s_ref, o_ref: None,
            grid=(b, hkv, s // bk),
            in_specs=[pl.BlockSpec((1, 1, bk), lambda bi, hi, ti: (bi, hi, ti))],
            out_specs=pl.BlockSpec((1, 1, bk), lambda bi, hi, ti: (bi, hi, ti)),
            out_shape=jnp.zeros(scale.shape, jnp.float32),
        )(scale)

    return oob_index_map, bad_divisibility, fp_materializing_quant, clean, bad_tiling


def test_kernel_checker_catches_oob_index_map():
    import jax.numpy as jnp
    from repro.analysis.kernel_check import KernelCase, check_op

    oob, _, _, _, _ = _bad_kernel_ops()
    found = check_op(oob, [KernelCase("oob", (jnp.zeros(64, jnp.float32),), {})])
    assert "kernel:index-oob" in _rules(found)


def test_kernel_checker_catches_block_divisibility():
    import jax.numpy as jnp
    from repro.analysis.kernel_check import KernelCase, check_op

    _, baddiv, _, _, _ = _bad_kernel_ops()
    found = check_op(
        baddiv, [KernelCase("div", (jnp.zeros(64, jnp.float32),), {})])
    assert "kernel:block-divisibility" in _rules(found)


def test_kernel_checker_catches_fp_cache_materialization():
    import jax.numpy as jnp
    from repro.analysis.kernel_check import KernelCase, check_op

    _, _, fpmat, _, _ = _bad_kernel_ops()
    k_q = jnp.zeros((4, 2, 128, 16), jnp.int8)
    k_scale = jnp.ones((4, 2, 128), jnp.float32)
    found = check_op(fpmat, [KernelCase(
        "quant", (k_q, k_scale), {}, fp_elems=int(np.prod(k_q.shape)))])
    assert "kernel:fp-cache-alloc" in _rules(found)


def test_kernel_checker_clean_op_passes():
    import jax.numpy as jnp
    from repro.analysis.kernel_check import KernelCase, check_op

    _, _, _, clean, _ = _bad_kernel_ops()
    found = check_op(
        clean,
        [KernelCase("ok", (jnp.zeros((64, 128), jnp.float32),), {}, fp_elems=10**9)])
    assert found == []


def test_kernel_checker_catches_tpu_block_tiling():
    """The scale-plane block (1, 1, bk) over (B, Hkv=24, S) passes interpret
    mode but Mosaic refuses it: second-minor 1 is neither a multiple of 8
    nor Hkv.  The same rows as a (1, 1, 1, bk) block of (B, Hkv, 1, S) pass."""
    import jax.numpy as jnp
    from repro.analysis.kernel_check import KernelCase, check_op, tpu_tiling_ok

    _, _, _, _, bad = _bad_kernel_ops()
    found = check_op(bad, [KernelCase("scale", (jnp.ones((2, 24, 512), jnp.float32),), {})])
    assert "kernel:block-tiling" in _rules(found)
    assert tpu_tiling_ok((1, 1, 1, 128), (2, 24, 1, 512), jnp.float32)
    assert tpu_tiling_ok((128,), (1024,), jnp.float32)
    assert not tpu_tiling_ok((128,), (1024,), jnp.int8)


# ---------------------------------------------------------------- baseline --

def test_baseline_format_and_suppression(tmp_path):
    f = Finding("lock", "lock:unguarded", "mod.py", 10, "msg")
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "# comment\n"
        f"{f.fingerprint} lock:unguarded mod.py — tracked debt, see #42\n")
    fps, errors = load_baseline(bl)
    assert errors == [] and fps == {f.fingerprint}
    active, suppressed = split_baselined([f], fps)
    assert active == [] and suppressed == [f]


def test_baseline_rejects_missing_reason_and_bad_fingerprint(tmp_path):
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "deadbeefcafe lock:unguarded mod.py\n"  # no reason
        "nothex lock:unguarded mod.py — why\n")  # malformed fingerprint
    fps, errors = load_baseline(bl)
    assert fps == set()
    assert len(errors) == 2
    assert "no reason" in errors[0]
    assert "malformed" in errors[1]


def test_fingerprint_is_line_number_independent():
    a = Finding("lock", "lock:unguarded", "mod.py", 10, "msg")
    b = Finding("lock", "lock:unguarded", "mod.py", 99, "msg")
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != Finding(
        "lock", "lock:unguarded", "mod.py", 10, "other").fingerprint


def test_fingerprint_folds_in_scope():
    """Identical messages in DIFFERENT functions must not collide — the
    scope (enclosing def) is part of the fingerprint."""
    a = Finding("lock", "lock:unguarded", "mod.py", 10, "msg", scope="A.f")
    b = Finding("lock", "lock:unguarded", "mod.py", 99, "msg", scope="B.g")
    assert a.fingerprint != b.fingerprint
    # both collapse to the same pre-scope (legacy) fingerprint
    assert a.legacy_fingerprint == b.legacy_fingerprint
    assert a.scope in a.render()


def test_legacy_fingerprint_still_suppresses_with_rewrite_hint():
    """A baseline written before scopes existed keeps suppressing, and the
    CLI surfaces a rewrite hint naming the new fingerprint."""
    from repro.analysis.common import legacy_hints

    f = Finding("det", "det:wallclock", "core.py", 5, "msg", scope="C.step")
    baseline = {f.legacy_fingerprint}
    active, suppressed = split_baselined([f], baseline)
    assert active == [] and suppressed == [f]
    hints = legacy_hints([f], baseline)
    assert len(hints) == 1
    assert f.fingerprint in hints[0] and f.legacy_fingerprint in hints[0]
    # an entry already using the scoped fingerprint needs no hint
    assert legacy_hints([f], {f.fingerprint}) == []


# ------------------------------------------------------------ program pass --

def _collect():
    got = []
    return got, lambda rule, msg: got.append(rule)


def test_progcheck_dtype_flow_catches_f64():
    import jax
    import jax.numpy as jnp
    from repro.analysis import progcheck

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jax.ShapeDtypeStruct((8,), jnp.float32))
    got, emit = _collect()
    progcheck.check_dtype_flow(closed, quantized=False,
                               fp_threshold_elems=10**9, emit=emit)
    assert "prog:f64" in got


def test_progcheck_catches_injected_fp_cache_dequant():
    """A quantized program that dequantizes the WHOLE KV cache into one
    f32 buffer (the jnp-fallback failure mode) must be flagged; a program
    under the threshold must not."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import progcheck

    k_q = jax.ShapeDtypeStruct((4, 2, 128, 16), jnp.int8)
    scale = jax.ShapeDtypeStruct((4, 2, 128), jnp.float32)
    closed = jax.make_jaxpr(
        lambda k, s: (k.astype(jnp.float32) * s[..., None]).sum())(k_q, scale)
    cache_elems = int(np.prod(k_q.shape))
    got, emit = _collect()
    progcheck.check_dtype_flow(closed, quantized=True,
                               fp_threshold_elems=cache_elems, emit=emit)
    assert got == ["prog:fp-cache-alloc"]
    # same program, fp cache: dequant-sized f32 buffers are legitimate
    got, emit = _collect()
    progcheck.check_dtype_flow(closed, quantized=False,
                               fp_threshold_elems=cache_elems, emit=emit)
    assert got == []
    # per-layer-view-sized intermediates stay under the threshold
    got, emit = _collect()
    progcheck.check_dtype_flow(closed, quantized=True,
                               fp_threshold_elems=2 * cache_elems, emit=emit)
    assert got == []


def test_progcheck_catches_dropped_cache_donation():
    """A cache-sized buffer threaded through a step program without
    donation doubles the KV footprint — the audit must flag exactly the
    undonated variant."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import progcheck

    tok = jax.ShapeDtypeStruct((), jnp.int32)
    cache = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def step(t, c):
        c = c.at[0, 0].set(t.astype(jnp.float32))
        return c[0, 0], c

    closed = jax.make_jaxpr(step)(tok, cache)
    inputs = (tok, cache)
    got, emit = _collect()
    progcheck.check_donation(closed, inputs, donate_argnums=(),
                             threshold_bytes=cache.size * 4, emit=emit)
    assert got == ["prog:cache-not-donated"]
    got, emit = _collect()
    progcheck.check_donation(closed, inputs, donate_argnums=(1,),
                             threshold_bytes=cache.size * 4, emit=emit)
    assert got == []
    # small threaded values (sampler seeds and friends) never trigger
    got, emit = _collect()
    progcheck.check_donation(closed, inputs, donate_argnums=(),
                             threshold_bytes=10**9, emit=emit)
    assert got == []


def test_progcheck_catches_cost_drift():
    from repro.analysis import progcheck

    def row(ratio):
        return dict(layout="contiguous", kv_dtype="int8", program="decode:x",
                    kind="kv_stream_bytes", counted=ratio * 100.0,
                    bound=100.0, ratio=ratio, tol_lo=0.87, tol_hi=1.15)

    got, emit = _collect()
    progcheck.cost_findings([row(1.0), row(1.14)], lambda r: emit)
    assert got == []
    got, emit = _collect()
    progcheck.cost_findings([row(2.0), row(0.4)], lambda r: emit)
    assert got == ["prog:cost-drift", "prog:cost-drift"]


class _StubEngine:
    def __init__(self):
        self.programs = {}


class _StubProgram:
    def __init__(self):
        self.abstract_inputs = ((),)


class _BucketStub:
    """Minimal ModelRunner bucket surface: quantum-aligned, covering, and
    closed over the built grid."""
    cache_layout = "contiguous"
    prompt_len = 8
    max_len = 32
    prefill_chunk = None

    def __init__(self):
        self.engine = _StubEngine()

    def bucket(self, n):
        b = -(-n // 8) * 8
        return min(b, self.max_len)

    def reachable_buckets(self):
        return sorted({self.bucket(n) for n in range(1, self.max_len + 1)})

    def progs(self, b):
        self.engine.programs.setdefault(f"prefill:{b}", _StubProgram())
        return {}

    def program_signatures(self):
        return dict(self.engine.programs)


def test_progcheck_bucket_coverage_clean_stub():
    from repro.analysis import progcheck

    runner = _BucketStub()
    for b in runner.reachable_buckets():
        runner.progs(b)  # the "built grid"
    got, emit = _collect()
    progcheck.check_bucket_coverage(runner, emit)
    assert got == []


def test_progcheck_catches_bucket_shape_leak():
    """bucket(n) = n (per-prompt shapes) blows the O(log) cardinality
    promise — the production recompile-storm failure mode."""
    from repro.analysis import progcheck

    class Leaky(_BucketStub):
        def bucket(self, n):
            return n

    got, emit = _collect()
    progcheck.check_bucket_coverage(Leaky(), emit)
    assert "prog:shape-leak" in got


def test_progcheck_catches_grid_closure_leak():
    """A program registered only when dispatch asks for it (not by
    build_serving_grid) is a per-request recompile — the closure check
    must see the registry grow."""
    from repro.analysis import progcheck

    runner = _BucketStub()  # grid NOT built: every progs() call registers
    got, emit = _collect()
    progcheck.check_bucket_coverage(runner, emit)
    assert "prog:shape-leak" in got


def test_progcheck_catches_noncovering_bucket():
    from repro.analysis import progcheck

    class Truncating(_BucketStub):
        def bucket(self, n):
            return 8  # every prompt padded DOWN to 8: truncation

    got, emit = _collect()
    progcheck.check_bucket_coverage(Truncating(), emit)
    assert "prog:shape-leak" in got


def _fake_ops_module(tmp_path, name, ns):
    import types

    mod = types.ModuleType(name)
    src = tmp_path / f"{name}.py"
    src.write_text("# fixture ops module\n")
    mod.__file__ = str(src)
    for k, v in ns.items():
        setattr(mod, k, v)
    return mod


def test_progcheck_flags_missing_and_malformed_op_annotations(tmp_path):
    from repro.analysis import progcheck

    got, emit = _collect()
    emit_at = lambda path, line, scope="": emit  # noqa: E731
    bare = _fake_ops_module(tmp_path, "bare_ops", {})
    progcheck.check_op_contracts(emit_at, modules=[bare])
    assert got == ["prog:op-annotation"]

    def my_op(q, k, v):
        return q

    got, emit = _collect()
    emit_at = lambda path, line, scope="": emit  # noqa: E731
    bad = _fake_ops_module(tmp_path, "bad_ops", {
        "my_op": my_op,
        "CACHE_OPERANDS": {
            "my_op": {"args": ("k", "nope"), "writes": False},  # unknown arg
            "ghost": {"args": ("k",), "writes": False},  # missing callable
            "my_op2": None,
        },
        "my_op2": my_op,
    })
    bad.CACHE_OPERANDS["my_op2"] = {"args": ("k",), "writes": True}
    progcheck.check_op_contracts(emit_at, modules=[bad])
    assert sorted(got) == ["prog:op-annotation"] * 3


def test_progcheck_catches_cache_passthrough_alias(tmp_path):
    """A declared read-only entry returning its cache operand unchanged is
    an aliasing violation; a computing entry is not."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import progcheck

    s = jax.ShapeDtypeStruct

    def passthrough(q, k):
        return q + 1.0, k  # hands the cache buffer back out

    def computes(q, k):
        return (q[:, None, :] * k).sum(1)

    probe = ((s((4, 8), jnp.float32), s((4, 8), jnp.float32)), {})
    mod = _fake_ops_module(tmp_path, "alias_ops", {
        "passthrough": passthrough,
        "computes": computes,
        "CACHE_OPERANDS": {
            "passthrough": {"args": ("k",), "writes": False},
            "computes": {"args": ("k",), "writes": False},
        },
        "_ANALYSIS_PROBES": {"passthrough": probe, "computes": probe},
    })
    got, emit = _collect()
    progcheck.check_op_contracts(
        lambda path, line, scope="": emit, modules=[mod])
    assert got == ["prog:op-alias"]


def test_program_pass_foreign_root_reports_clean(tmp_path):
    """The program pass audits the imported package; fixture trees have no
    programs to trace and must come back clean (not crash)."""
    root = _tree(tmp_path, {"mod.py": "x = 1\n"})
    assert run_passes(["program"], root=root)["program"] == []


def test_cli_rejects_unknown_pass_listing_valid_names(capsys):
    from repro.analysis.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["--pass", "bogus"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    for name in ("lock", "kernel", "determinism", "program"):
        assert name in err


# -------------------------------------------------------------- clean tree --

def test_real_tree_has_no_unbaselined_findings():
    """The CI gate, as a test: every pass over the real src/repro must be
    clean modulo the checked-in baseline."""
    results = run_passes(["lock", "kernel", "determinism", "program"],
                         root=default_root())
    fps, errors = load_baseline(default_baseline())
    assert errors == []
    offenders = []
    for name, found in results.items():
        active, _ = split_baselined(found, fps)
        offenders += [f"[{name}] {f.render()}" for f in active]
    assert offenders == [], "\n".join(offenders)


def test_default_root_is_the_source_tree():
    assert default_root() == REPO_SRC


# --------------------------------------------- satellite: handoff counters --

def test_handoff_ship_counters_exact_under_contention():
    """ship() meters from the engine thread AND the pool thread; the lock
    the lint demanded must make the counters exact, not approximate."""
    from repro.serving.disagg.handoff import KVHandoffChannel

    chan = KVHandoffChannel()  # no mesh: passthrough, still metered
    payload = np.zeros(32, np.float32)
    per_thread, threads = 300, 4

    def hammer(eager):
        for _ in range(per_thread):
            chan.ship(payload, eager=eager)

    ts = [threading.Thread(target=hammer, args=(i % 2 == 1,))
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = per_thread * threads
    assert chan.segments == total
    assert chan.eager_segments == total // 2
    assert chan.bytes_shipped == total * payload.nbytes
    snap = chan.snapshot()
    assert snap["segments"] == total
    assert snap["pending"] == 0


# ------------------------------------- satellite: _deprioritize hardening --

def test_deprioritize_survives_permission_error(monkeypatch):
    from repro.serving.disagg import prefill_pool as pp

    def deny(*a, **k):
        raise PermissionError("RLIMIT_NICE")

    monkeypatch.setattr(os, "sched_setscheduler", deny, raising=False)
    monkeypatch.setattr(os, "setpriority", deny, raising=False)
    pp._deprioritize()  # must not raise


def test_deprioritize_survives_missing_apis(monkeypatch):
    from repro.serving.disagg import prefill_pool as pp

    monkeypatch.delattr(os, "sched_setscheduler", raising=False)
    monkeypatch.delattr(threading, "get_native_id", raising=False)
    pp._deprioritize()  # must not raise


def test_deprioritize_as_initializer_does_not_poison_executor(monkeypatch):
    """The failure mode the guard exists for: a raising initializer breaks
    the executor and every later submit dies with BrokenThreadPool."""
    from repro.serving.disagg import prefill_pool as pp

    def deny(*a, **k):
        raise PermissionError("denied")

    monkeypatch.setattr(os, "sched_setscheduler", deny, raising=False)
    monkeypatch.setattr(os, "setpriority", deny, raising=False)
    ex = ThreadPoolExecutor(max_workers=1, initializer=pp._deprioritize)
    try:
        assert ex.submit(lambda: 41 + 1).result(timeout=30) == 42
    finally:
        ex.shutdown(wait=True)


# ------------------------------- satellite: AsyncEngine loop-owned mirrors --

class _StubRunner:
    max_len = 128
    cache_layout = "contiguous"


class _StubScheduler:
    def __init__(self):
        self.queue = []

    def validate(self, req):
        pass


class _StubCore:
    """Just enough EngineCore surface for AsyncEngine admission paths."""

    def __init__(self):
        self.scheduler = _StubScheduler()
        self.runner = _StubRunner()


def _stub_engine(max_queue=4):
    from repro.serving.async_engine import AsyncEngine

    return AsyncEngine(_StubCore(), max_queue=max_queue)


def test_duplicate_id_rejected_even_after_stream_closed():
    """_ids (the loop-owned ever-admitted set) must keep rejecting a reused
    id after the stream is gone — the old code read core.finished, which
    the lint now forbids mid-step."""
    from repro.serving.async_engine import AdmissionRejected

    async def go():
        eng = _stub_engine()
        await eng.submit([1, 2, 3], request_id="r1", max_new=4)
        # simulate the stream finishing: _route deletes the stream entry,
        # but the id stays admitted forever
        del eng._streams["r1"]
        eng._pending.clear()
        with pytest.raises(AdmissionRejected) as exc:
            await eng.submit([1, 2, 3], request_id="r1", max_new=4)
        assert exc.value.reason.startswith("duplicate_id")
        assert eng.reject_reasons == {"duplicate_id": 1}

    asyncio.run(go())


def test_backlog_uses_between_quanta_snapshot_not_live_core():
    """Backpressure must consult _core_backlog (the mirror refreshed
    between quanta), never len(core.scheduler.queue) live."""
    from repro.serving.async_engine import AdmissionRejected

    async def go():
        eng = _stub_engine(max_queue=4)
        # live core queue says "full" but the snapshot says empty: admission
        # must trust the snapshot (the live read would race a quantum)
        eng.core.scheduler.queue = [object()] * 10
        await eng.submit([1], request_id="a", max_new=1)  # not rejected
        # snapshot says full -> rejected, even though we just emptied core
        eng.core.scheduler.queue = []
        eng._pending.clear()
        eng._core_backlog = eng.max_queue
        with pytest.raises(AdmissionRejected) as exc:
            await eng.submit([1], request_id="b", max_new=1)
        assert exc.value.reason.startswith("queue_full")

    asyncio.run(go())


def test_drain_control_refreshes_backlog_mirror():
    """_drain_control is the one place admission state touches the core:
    it must leave _core_backlog equal to the scheduler queue length."""

    async def go():
        eng = _stub_engine()
        submitted = []
        eng.core.submit = lambda req: (
            submitted.append(req), eng.core.scheduler.queue.append(req))
        await eng.submit([1], request_id="a", max_new=1)
        await eng.submit([2], request_id="b", max_new=1)
        eng._drain_control()
        assert [r.request_id for r in submitted] == ["a", "b"]
        assert eng._core_backlog == 2
        assert eng._backlog() == 2  # pending drained, mirror fresh

    asyncio.run(go())
