"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath it
comes out not correct, once for each fault a one-chip serving cell can
have.  (The exchange between chips does not exist on one chip.)"""
import contextlib
import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cell_run
from bench.tests import tiny


def alter_token(eng):
    """A token altered where it is produced: every decode token + 1."""
    sample = eng.runner.sample_batch
    eng.runner.sample_batch = lambda lg, inf: sample(lg, inf) + 1


def state_unchanged(eng):
    """A decode step that returns its cache unchanged (no KV written)."""
    prog = eng.runner.decode_prog
    fn = prog.fn

    def unchanged(params, tok, cache, lengths):
        keep = jax.tree.map(jnp.copy, cache)  # the call donates its cache
        return fn(params, tok, cache, lengths)[0], keep
    prog.fn = unchanged


def half_batch(eng):
    """Half of the decode batch left out: odd slots repeat their last token."""
    r = eng.runner
    sample = r.sample_batch

    def fn(lg, inf):
        new = sample(lg, inf)
        odd = jnp.arange(new.shape[0]) % 2 == 1
        return jnp.where(odd, r.last_tokens, new)
    r.sample_batch = fn


def run(patch=None, mix=tiny.CLOSED, engine=tiny.CONTIGUOUS):
    return cell_run.execute(tiny.cell(mix=mix, engine=engine), 2**31 + 77, 1.0, False,
                            t_start=time.perf_counter(), require_tpu=False, patch=patch)


@pytest.mark.parametrize("mix,engine", [(tiny.CLOSED, tiny.CONTIGUOUS), (tiny.OPEN, tiny.PAGED)],
                         ids=["closed-contiguous", "open-paged"])
def test_sound_run_is_correct(mix, engine):
    r = run(mix=mix, engine=engine)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "check"  # the numbers compared come last
    assert {"itl_p95_ms", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("fault", [alter_token, state_unchanged, half_batch],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault):
    r = run(patch=fault)
    assert not r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] > r["check"]["logit_gap"]["limit"]


def test_control_run_is_not_correct():
    """The configuration's control put in the program's place through the
    whole run (``execute(..., control=...)``, as ``bench/calibrate.py``
    reads it on the chip) comes out not correct."""
    c = tiny.cell()
    r = cell_run.execute(c, 2**31 + 77, 1.0, False, t_start=time.perf_counter(),
                         require_tpu=False, control=c.config["control"])
    assert not r["correct"], r["check"]
    assert r["check"]["control_gap"]["value"] > r["check"]["control_gap"]["limit"]
    assert r["readings"]["served"]["logit_gap"] <= r["check"]["control_gap"]["limit"]


def test_no_tpu_no_result():
    from bench import run as bench_run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", "bitnet-730m.longdoc_decode", "--seed", "1",
                             "--seconds", "1", "--trace", "0"])
    assert rc != 0 and out.getvalue() == ""


def test_sample_holds_the_longest():
    from bench.harness import check
    from bench.harness.window import Stream

    class R:
        def __init__(self, n):
            self.out_tokens = list(range(n))

    streams = [Stream(f"r{i}", 0.0, 0.0, 5, done=1.0) for i in range(6)]
    reqs = {f"r{i}": R(n) for i, n in enumerate([3, 9, 1, 4, 4, 2])}
    picked = check.sample(streams, reqs, 3, np.random.default_rng(0))
    assert picked[0] == "r1" and len(set(picked)) == 3
