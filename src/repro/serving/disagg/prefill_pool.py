"""The prefill pool: compute-phase programs resident on their own mesh.

One ``PrefillPool`` owns everything the prefill phase needs and nothing the
decode phase does: a ``PhaseEngine`` over the prefill mesh (tensor-parallel
via the ``launch.sharding_rules`` inference specs when meshed), a second
committed copy of the params (the "static region" is replicated across
pools — weights never cross the handoff channel), the per-bucket
body/tail/full/relayout programs, and the fp chunk-prefix mirror for chunked
prefill.  The decode pool (``DisaggRunner``) calls in here for every prefill
forward and receives KV to ship through the ``KVHandoffChannel``.

Bit-identity with the colocated engine comes from running the SAME program
bodies on the same inputs: ``prefill_split_programs_varlen`` /
``prefill_program_varlen`` / ``prefill_chunk_kv_program`` share their math
with the fused colocated programs, and the contiguous relayout (pad +
layer-major->batch-major + quantize-on-write) runs prefill-side with the
exact ops ``ModelRunner`` uses, so the shipped pytree holds the bytes the
colocated install would have written.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.phase_engine import PhaseEngine, PhaseProgram
from repro.launch.sharding_rules import params_shardings
from repro.models.transformer import convert_for_inference
from repro.serving.paging import cdiv


def _deprioritize() -> None:
    """Drop the pool's dispatch thread to the lowest scheduling priority.
    Decode is the latency-critical phase: on hosts where both pools'
    programs end up competing for the same CPU cycles (the forced-device
    simulation, or a real mesh whose host runtime threads share cores),
    prefill work should only ever consume cycles decode leaves idle.
    On real two-pool hardware the prefill devices are dedicated, so this
    costs nothing there; no-op where the host forbids it.

    ``SCHED_IDLE`` beats plain nice 19: a nice-19 thread still holds the
    core for a wakeup-granularity slice (~ms) after a decode thread
    unblocks, which is exactly the tail this pool must not add, while an
    idle-class thread is preempted immediately by any normal-class wakeup.

    Best-effort by construction: this runs as the pool executor's
    *initializer*, and an initializer that raises poisons the executor —
    every later ``submit()`` fails with BrokenThreadPool and the pool is
    dead.  So every path degrades silently: missing APIs (non-Linux,
    no ``os.sched_setscheduler`` / ``os.setpriority`` /
    ``threading.get_native_id``), ``PermissionError`` (RLIMIT_NICE,
    containers dropping CAP_SYS_NICE), or any other host quirk just leaves
    the thread at normal priority — strictly a performance matter.
    """
    try:
        os.sched_setscheduler(0, os.SCHED_IDLE, os.sched_param(0))
        return
    except (AttributeError, OSError, ValueError):  # non-Linux / forbidden
        pass
    try:
        # PermissionError (an OSError) covers RLIMIT_NICE denials; the
        # getattr covers platforms where get_native_id does not exist at
        # all (threading exposes it only where the OS can name threads)
        get_native_id = getattr(threading, "get_native_id", None)
        if get_native_id is not None:
            os.setpriority(os.PRIO_PROCESS, get_native_id(), 19)
    except (AttributeError, OSError, ValueError):
        pass


class PrefillPool:
    """Phase-specialized prefill engine for one pool of devices."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        mesh=None,
        max_len: int,
        mode: str = "pdswap",  # "pdswap" | "static"
        cache_layout: str = "contiguous",
        block_size: int = 16,
        kv_dtype: str = "fp",
        prefill_chunk: Optional[int] = None,
    ):
        from repro.quant.kv_quant import quantize_kv_tree

        assert mode in ("pdswap", "static"), mode
        self.cfg = cfg
        self.mesh = mesh
        self.mode = mode
        self.cache_layout = cache_layout
        self.kv_dtype = kv_dtype
        self.max_len = max_len
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.engine = PhaseEngine(
            cfg, mesh, max_len=max_len, cache_layout=cache_layout,
            kv_dtype=kv_dtype)
        # this pool's dispatch thread: JAX's CPU client admits ONE inflight
        # computation per dispatching thread, so a chunk program launched
        # from the engine thread would stall that thread's next decode
        # dispatch behind the whole chunk — the overlap the split exists for
        # only becomes real when prefill work enters from its own thread
        self._exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="prefill-pool",
            initializer=_deprioritize)
        # this pool's own packed copy of the ternary linears (as ModelRunner)
        params = convert_for_inference(cfg, params)
        if mesh is not None:
            # commit this pool's copy of the static region to its own mesh;
            # the decode pool keeps its own committed copy — weights never
            # ride the handoff channel
            params = jax.device_put(
                params,
                params_shardings(jax.eval_shape(lambda: params), cfg, mesh,
                                 train=False))
        self.params = params
        self._pa = jax.eval_shape(lambda: params)

        if cache_layout != "paged":
            def relay_static(kv):  # same ops as ModelRunner.relay_static, so
                # the shipped decode-layout tree is byte-identical to what
                # the colocated static engine installs
                def pad(x):
                    p = [(0, 0)] * x.ndim
                    p[-2] = (0, max_len - x.shape[-2])
                    return jnp.moveaxis(jnp.pad(x, p), 0, 1)

                return quantize_kv_tree(jax.tree.map(pad, kv), kv_dtype)

            self.relay_static = jax.jit(relay_static)

        # fp chunk-prefix mirror, prefill-pool-resident: chunked prefill's
        # attention context lives where the chunks compute, and the decode
        # pool never holds it (DisaggRunner frees its own); only the
        # dispatch thread may touch it after construction (the single
        # worker serializes chunk order through the donated buffer)
        self.chunk_prefix = None  # owned-by: prefill-pool
        if prefill_chunk is not None:
            from repro.layers.attention import KVCache

            cap = (cdiv(max_len, block_size) * block_size
                   if cache_layout == "paged" else max_len)
            shape = (cfg.num_layers, 1, cfg.num_kv_heads, cap, cfg.head_dim)
            self.chunk_prefix = KVCache(jnp.zeros(shape, jnp.float32),
                                        jnp.zeros(shape, jnp.float32))

    # ------------------------------------------------------------ dispatch --

    def submit(self, fn: Callable) -> Future:
        """Run ``fn`` (a chunk compute + ship closure) on the pool's
        dedicated dispatch thread.  The single worker keeps chunk order —
        the donated chunk-prefix buffer threads sequentially through it —
        while the engine thread stays free to dispatch decode rounds that
        execute concurrently on the decode pool."""
        return self._exec.submit(fn)

    # ------------------------------------------------------------ programs --

    def progs(self, bucket: int) -> dict:
        """Prefill-phase programs for one prompt bucket (PhaseEngine caches
        by key, so this is build-once like ``ModelRunner.progs``).  The
        contiguous relayout runs HERE — the swap payload crosses the pool
        boundary already in decode layout (quantized payload+scales
        included), so the transfer moves the packed bytes, not fp."""
        p: dict = {}
        if self.mode == "pdswap":
            p["body"], p["tail"] = self.engine.prefill_split_programs_varlen(
                self._pa, 1, bucket)
        else:
            p["full"] = self.engine.prefill_program_varlen(self._pa, 1, bucket)
        if self.cache_layout != "paged" and self.mode == "pdswap":
            p["relayout"] = self.engine.relayout_program(1, bucket, self.max_len)
        return p

    def chunk_kv_prog(self, padded: int, prefix_width: int) -> PhaseProgram:
        """The compute-only chunk program (``prefill_chunk_kv_program``) for
        one (padded chunk length, prefix width) pair."""
        return self.engine.prefill_chunk_kv_program(padded, prefix_width)
