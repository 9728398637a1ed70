"""Phase-specialized compiled programs — the TPU analogue of the paper's
reconfigurable modules (DESIGN.md §2, C1).

On an FPGA a "configuration" is a bitstream; on TPU it is a compiled XLA
executable: fusion plan, kernel block shapes, layouts and collective
schedule.  ``PhaseEngine`` owns, for one (arch x mesh x shape):

  * ``prefill``        — token-parallel program (compute-optimized RM)
  * ``prefill_body``   — prefill through the LAST layer's attention
  * ``prefill_tail``   — last FFN + norm + logits (runs during the swap)
  * ``kv_relayout``    — the *swap itself*: prefill-layout KV -> decode-layout
                         cache (reshard + pad + optional int8 compression).
                         This is the physically-real analogue of the 45 ms
                         PCAP bitstream load.
  * ``decode``         — KV-streaming program (bandwidth-optimized RM)

Weights are never touched by the swap: both phase programs consume the same
param buffers with identical shardings — the paper's static region.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import kernels
from repro.configs.base import ModelConfig
from repro.layers.sharding import (
    DECODE_RULES,
    LONG_DECODE_RULES,
    MeshAxes,
    NULL_CTX,
    PREFILL_RULES,
    PartitionCtx,
)
from repro.models import get_model
from repro.launch.sharding_rules import params_shardings


@dataclasses.dataclass
class PhaseProgram:
    name: str
    fn: Callable  # jitted
    abstract_inputs: tuple = ()
    lowered: Any = None
    compiled: Any = None
    # Registry metadata, audited by the `program` analysis pass
    # (repro.analysis.progcheck): the donation DECLARED for this program —
    # recorded by PhaseEngine._program from the same tuple passed to
    # jax.jit(donate_argnums=...), so declaration and jit signature cannot
    # diverge — and the serving phase the program belongs to.
    donate_argnums: Tuple[int, ...] = ()
    phase: str = ""  # "prefill" | "decode" | "swap" | "sampler"

    def lower_and_compile(self, *args):
        args = args or self.abstract_inputs
        self.lowered = self.fn.lower(*args)
        self.compiled = self.lowered.compile()
        return self.compiled


def program_name(key: str, phase: str = "") -> str:
    """The jitted function's name for the program registered under ``key``:
    the key with every run of other characters than letters, digits and
    ``_`` made one ``_``, led by ``swap_`` for the swap phase's programs
    (``relayout:1x512->4160`` -> ``swap_relayout_1x512_4160``)."""
    name = re.sub(r"\W+", "_", key).strip("_")
    return f"swap_{name}" if phase == "swap" and not name.startswith("swap") else name


def _mesh_axes(mesh: Optional[Mesh]) -> MeshAxes:
    if mesh is None:
        return MeshAxes()
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data")) or (None,)
    dp = dp[0] if len(dp) == 1 else dp
    return MeshAxes(dp=dp, tp="model" if "model" in names else None, fsdp="data" if "data" in names else None)


def make_pctx(mesh: Optional[Mesh], phase: str) -> PartitionCtx:
    rules = {"prefill": PREFILL_RULES, "decode": DECODE_RULES, "long_decode": LONG_DECODE_RULES}.get(
        phase, PREFILL_RULES
    )
    return PartitionCtx(mesh=mesh, axes=_mesh_axes(mesh), rules=rules)


class PhaseEngine:
    """Builds and caches the phase programs for one architecture."""

    def __init__(
        self,
        cfg: ModelConfig,
        mesh: Optional[Mesh] = None,
        *,
        max_len: int = 0,
        long_context: bool = False,
        kv_quant: Optional[str] = None,  # legacy knob: None | "int8" ((int8, scale) tuples)
        cache_layout: str = "contiguous",  # "contiguous" | "paged"
        kv_dtype: str = "fp",  # "fp" | "int8" | "int4" — quantized KV subsystem
    ):
        from repro.quant.kv_quant import assert_kv_dtype

        assert cache_layout in ("contiguous", "paged"), cache_layout
        assert_kv_dtype(kv_dtype)
        assert kv_quant is None or kv_dtype == "fp", (
            "kv_quant (legacy relayout-only int8) and kv_dtype (the quantized "
            "KV-cache subsystem) are mutually exclusive")
        self.cfg = cfg
        self.mesh = mesh
        # GSPMD partitions the programs over several devices
        self.spmd = mesh is not None and mesh.size > 1
        self.api = get_model(cfg)
        self.max_len = max_len
        self.kv_quant = kv_quant
        self.kv_dtype = kv_dtype
        self.cache_layout = cache_layout
        self.decode_phase = "long_decode" if long_context else "decode"
        self.prefill_ctx = make_pctx(mesh, "prefill")
        self.decode_ctx = make_pctx(mesh, self.decode_phase)
        self._programs: Dict[str, PhaseProgram] = {}

    # ------------------------------------------------------------ helpers --

    def param_shardings(self, params_abstract):
        if self.mesh is None:
            return None
        return params_shardings(params_abstract, self.cfg, self.mesh, train=False)

    def _sd(self, pctx: PartitionCtx, *logical):
        return pctx.named_sharding(*logical)

    def _jit(self, fn, in_shardings=None, out_shardings=None, donate=()):
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        return jax.jit(fn, in_shardings=in_shardings, out_shardings=out_shardings, donate_argnums=donate)

    def _program(self, key: str, fn, *, in_shardings=None, out_shardings=None,
                 donate: Tuple[int, ...] = (), phase: str = "") -> PhaseProgram:
        """Jit ``fn`` and register it under ``key`` with its metadata.  The
        ONE construction path for phase programs: ``donate`` is both the
        ``jax.jit(donate_argnums=...)`` argument and the program's declared
        donation, so the registry the analysis pass audits reflects what the
        compiler was actually told.  The jitted function is named
        ``program_name(key, phase)``, so the compiled module (and a profiler
        trace) reads ``jit_decode_4x9216``, not ``jit_fn``.  A program
        over a mesh of several devices is traced ``kernels.partitioned``:
        GSPMD cannot partition a Mosaic kernel."""
        @functools.wraps(fn)
        def named(*args):
            with kernels.partitioned(self.spmd):
                return fn(*args)

        named.__name__ = named.__qualname__ = program_name(key, phase)
        prog = PhaseProgram(
            key,
            self._jit(named, in_shardings=in_shardings,
                      out_shardings=out_shardings, donate=donate),
            donate_argnums=tuple(donate),
            phase=phase,
        )
        self._programs[key] = prog
        return prog

    @property
    def programs(self) -> Dict[str, PhaseProgram]:
        """The program registry (a copy): every phase program built so far,
        keyed by its cache signature — the surface the `program` analysis
        pass traces."""
        return dict(self._programs)

    # ----------------------------------------------------------- programs --

    def prefill_program(self, params_abstract, batch: int, seq: int, *, frames: bool = False) -> PhaseProgram:
        key = f"prefill:{batch}x{seq}"
        if key in self._programs:
            return self._programs[key]
        cfg, api, pctx = self.cfg, self.api, self.prefill_ctx

        if frames:
            def fn(params, tokens, frame_emb):
                return api.forward_prefill(params, tokens, cfg, pctx, frames=frame_emb)
        else:
            def fn(params, tokens):
                return api.forward_prefill(params, tokens, cfg, pctx)

        in_sh = None
        if self.mesh is not None:
            tok_sh = self._sd(pctx, "batch", "seq")
            in_sh = (self.param_shardings(params_abstract), tok_sh)
            if frames:
                in_sh = in_sh + (self._sd(pctx, "batch", "seq", "embed"),)
        return self._program(key, fn, in_shardings=in_sh, phase="prefill")

    def prefill_program_varlen(self, params_abstract, batch: int, seq: int) -> PhaseProgram:
        """Prefill compiled at bucket length ``seq`` for right-padded
        variable-length prompts: ``fn(params, tokens, last_pos)`` returns the
        logits of the prompt's true last token (causality keeps positions
        <= last_pos independent of the padding tail)."""
        key = f"prefill_varlen:{batch}x{seq}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.prefill_ctx
        assert cfg.family == "transformer", "varlen prefill implemented for the transformer family"
        from repro.models import transformer as T

        def fn(params, tokens, last_pos):
            return T.forward_prefill(params, tokens, cfg, pctx, last_pos=last_pos)

        in_sh = None
        if self.mesh is not None:
            in_sh = (self.param_shardings(params_abstract), self._sd(pctx, "batch", "seq"), None)
        return self._program(key, fn, in_shardings=in_sh, phase="prefill")

    def prefill_split_programs_varlen(
        self, params_abstract, batch: int, seq: int
    ) -> Tuple[PhaseProgram, PhaseProgram]:
        """(body, tail) like ``prefill_split_programs`` but the tail takes
        ``last_pos`` — the overlap split for variable-length prompts."""
        key = f"prefill_split_varlen:{batch}x{seq}"
        if key in self._programs:
            body = self._programs[key]
            tail = self._programs[key + ":tail"]
            return body, tail
        cfg, pctx = self.cfg, self.prefill_ctx
        assert cfg.family == "transformer", "overlap split implemented for the transformer family"
        from repro.models import transformer as T

        def body_fn(params, tokens):
            return T.forward_prefill(params, tokens, cfg, pctx, split_tail=True)

        def tail_fn(params, x_mid, last_pos):
            return T.prefill_tail(params, x_mid, cfg, pctx, last_pos=last_pos)

        in_body = in_tail = None
        if self.mesh is not None:
            psh = self.param_shardings(params_abstract)
            in_body = (psh, self._sd(pctx, "batch", "seq"))
            in_tail = (psh, self._sd(pctx, "batch", "seq", "embed"), None)
        body = self._program(key, body_fn, in_shardings=in_body, phase="prefill")
        tail = self._program(key + ":tail", tail_fn, in_shardings=in_tail, phase="prefill")
        return body, tail

    def prefill_split_programs(self, params_abstract, batch: int, seq: int) -> Tuple[PhaseProgram, PhaseProgram]:
        """(body, tail): the overlap split at the last layer's attention."""
        cfg, pctx = self.cfg, self.prefill_ctx
        assert cfg.family == "transformer", "overlap split implemented for the transformer family"
        from repro.models import transformer as T

        def body_fn(params, tokens):
            return T.forward_prefill(params, tokens, cfg, pctx, split_tail=True)

        def tail_fn(params, x_mid):
            return T.prefill_tail(params, x_mid, cfg, pctx)

        in_body = in_tail = None
        if self.mesh is not None:
            psh = self.param_shardings(params_abstract)
            in_body = (psh, self._sd(pctx, "batch", "seq"))
            in_tail = (psh, self._sd(pctx, "batch", "seq", "embed"))
        body = self._program(f"prefill_body:{batch}x{seq}", body_fn,
                             in_shardings=in_body, phase="prefill")
        tail = self._program(f"prefill_tail:{batch}x{seq}", tail_fn,
                             in_shardings=in_tail, phase="prefill")
        return body, tail

    def prefill_chunk_program(
        self, chunk: int, n_slots: int, max_len: int, prefix_width: int
    ) -> PhaseProgram:
        """Chunked prefill against the contiguous decode cache:
        ``fn(params, tokens (1, C), cache, prefix, slot, prefix_len,
        last_pos) -> (logits, new_cache, new_prefix)`` (cache and the fp
        prefix mirror both donated — the chunk installs its KV in place,
        quantize-on-write under ``kv_dtype``).

        This is the bounded-quantum prefill RM: ONE compiled shape per
        chunk size serves every prompt (plus one tail bucket per prompt),
        replacing the per-prompt power-of-two bucket ladder.  The swap is
        fused into the program — each chunk both computes and installs its
        KV, so the fabric can flip back to decode after every quantum.
        ``slot``/``prefix_len``/``last_pos`` are traced scalars: no
        recompilation across slots or chunk indices; ``prefix_width`` is
        compile-time (the runner's geometric ladder over the prefix), so
        short prompts never pay attention over the mirror's full max_len
        capacity.  No pinned in_shardings: the serving core runs these
        unsharded today, and under a mesh GSPMD propagates from the
        committed param/cache buffers (pinning the full tuple like the
        monolithic programs do is future work)."""
        key = f"prefill_chunk:{chunk}+{prefix_width}@{n_slots}x{max_len}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.prefill_ctx
        assert cfg.family == "transformer", "chunked prefill implemented for the transformer family"
        from repro.models import transformer as T

        def fn(params, tokens, cache, prefix, slot, prefix_len, last_pos):
            return T.prefill_chunk(params, tokens, cache, prefix, slot,
                                   prefix_len, last_pos, cfg, pctx,
                                   prefix_width=prefix_width)

        return self._program(key, fn, donate=(2, 3), phase="prefill")

    def paged_prefill_chunk_program(
        self, chunk: int, max_pages: int, block_size: int, prefix_width: int
    ) -> PhaseProgram:
        """Chunked prefill against the paged pool: ``fn(params, tokens
        (1, C), pages, prefix, page_ids (C/bs,), prefix_len, last_pos) ->
        (logits, new_pages, new_prefix)`` (pool and fp prefix mirror both
        donated).  ``C`` must be a multiple of ``block_size``; the chunk's
        pages are written by the same quantize-on-write scatter the
        monolithic page-write swap uses, with prefix-cache-hit pages
        skipped via out-of-bounds ids.  ``prefix_width`` / sharding: see
        ``prefill_chunk_program`` (unsharded today; GSPMD propagates)."""
        key = f"prefill_chunk_paged:{chunk}+{prefix_width}@{max_pages}x{block_size}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.prefill_ctx
        assert cfg.family == "transformer", "chunked prefill implemented for the transformer family"
        assert chunk % block_size == 0, (chunk, block_size)
        from repro.models import transformer as T

        def fn(params, tokens, pages, prefix, page_ids, prefix_len, last_pos):
            return T.prefill_chunk_paged(params, tokens, pages, prefix,
                                         page_ids, prefix_len, last_pos, cfg,
                                         pctx, prefix_width=prefix_width)

        return self._program(key, fn, donate=(2, 3), phase="prefill")

    def prefill_chunk_kv_program(self, chunk: int, prefix_width: int) -> PhaseProgram:
        """Compute-only chunked prefill — the disaggregated prefill pool's
        chunk RM: ``fn(params, tokens (1, C), prefix, prefix_len, last_pos)
        -> (logits, chunk_kv, new_prefix)`` (fp prefix mirror donated).
        Same body and logits epilogue as the fused chunk programs; the
        chunk's fp KV is returned for the handoff channel to ship, and the
        decode pool installs it with the SAME quantize-on-write scatter the
        colocated engine fuses in (``chunk_write_program`` /
        ``page_write_program``) — the install split that keeps the two-pool
        engine bit-identical.  No pinned in_shardings, matching the fused
        chunk programs (GSPMD propagates from the committed params)."""
        key = f"prefill_chunk_kv:{chunk}+{prefix_width}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.prefill_ctx
        assert cfg.family == "transformer", "chunked prefill implemented for the transformer family"
        from repro.models import transformer as T

        def fn(params, tokens, prefix, prefix_len, last_pos):
            return T.prefill_chunk_kv(params, tokens, prefix, prefix_len,
                                      last_pos, cfg, pctx,
                                      prefix_width=prefix_width)

        return self._program(key, fn, donate=(2,), phase="prefill")

    def chunk_write_program(self, chunk: int) -> PhaseProgram:
        """Decode-side install of one shipped prefill chunk into the
        CONTIGUOUS cache: ``fn(cache, kv, slot, prefix_len) -> new_cache``
        (cache donated).  The exact ``write_chunk_kv_q`` scatter
        (quantize-on-write under ``kv_dtype``) the fused
        ``prefill_chunk_program`` runs — split out so the disaggregated
        decode pool installs handoff chunks with the colocated engine's
        bytes.  The paged counterpart is ``page_write_program``."""
        key = f"chunk_write:{chunk}"
        if key in self._programs:
            return self._programs[key]
        from repro.layers.attention import KVCache, write_chunk_kv_q

        def fn(cache, kv, slot, prefix_len):
            return KVCache(
                write_chunk_kv_q(cache.k, kv.k, slot, prefix_len),
                write_chunk_kv_q(cache.v, kv.v, slot, prefix_len),
            )

        return self._program(key, fn, donate=(0,), phase="swap")

    def relayout_program(self, batch: int, seq: int, max_len: int) -> PhaseProgram:
        """The swap: prefill-layout KV -> decode-layout cache buffer.

        Implements (i) the reshard from prefill sharding (batch x heads) to
        decode sharding (batch x *sequence*) — the collective this program
        pays is the TPU bitstream-load analogue; (ii) right-padding into the
        persistent decode buffer; (iii) with ``kv_dtype`` in {"int8",
        "int4"}, quantize-on-write into packed payload + fp32 scale planes
        (halving/quartering decode KV traffic — the subsystem's Eq. (5)
        lever); the legacy ``kv_quant="int8"`` knob keeps its (int8, scale)
        tuple output.
        """
        cfg, pctx = self.cfg, self.decode_ctx
        key = f"relayout:{batch}x{seq}->{max_len}"
        if key in self._programs:
            return self._programs[key]

        def fn(kv):
            def relay(x):  # prefill layout (L, B, Hkv, S, D)
                pad = [(0, 0)] * x.ndim
                pad[-2] = (0, max_len - x.shape[-2])
                y = jnp.pad(x, pad)
                # the layout swap proper: layer-major (prefill writes KV per
                # layer) -> batch-leading decode layout (token-granular
                # in-place appends; see attention.scatter_new_tokens)
                y = jnp.moveaxis(y, 0, 1)
                return pctx.shard(y, "batch", "layers", "kv_heads", "kv_seq", "head_dim")

            kv = jax.tree.map(relay, kv)
            if self.kv_quant == "int8":
                def q(x):
                    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0 + 1e-8
                    return (x / s).astype(jnp.int8), s.astype(jnp.float32)
                return jax.tree.map(q, kv)
            if self.kv_dtype != "fp":
                from repro.quant.kv_quant import quantize_kv_tree

                kv = quantize_kv_tree(kv, self.kv_dtype)
            return kv

        return self._program(key, fn, phase="swap")

    def decode_program(self, params_abstract, batch: int, max_len: int) -> PhaseProgram:
        key = f"decode:{batch}x{max_len}"
        if key in self._programs:
            return self._programs[key]
        cfg, api, pctx = self.cfg, self.api, self.decode_ctx

        def fn(params, token, cache, lengths):
            return api.decode_step(params, token, cache, lengths, cfg, pctx)

        in_sh = None
        if self.mesh is not None:
            psh = self.param_shardings(params_abstract)
            tok_sh = self._sd(pctx, "batch")
            cache_sh = self.cache_shardings(batch, max_len)
            in_sh = (psh, tok_sh, cache_sh, self._sd(pctx, "batch"))
        return self._program(key, fn, in_shardings=in_sh, donate=(2,),
                             phase="decode")

    def paged_decode_program(self, params_abstract, n_slots: int, max_pages: int) -> PhaseProgram:
        """Decode over the paged cache: ``fn(params, token, pages,
        block_tables, lengths) -> (logits, new_pages)``.  The page pool is
        donated (in-place append, like the contiguous decode buffer)."""
        key = f"decode_paged:{n_slots}x{max_pages}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.decode_ctx
        assert cfg.family == "transformer", "paged decode implemented for the transformer family"
        from repro.models import transformer as T

        def fn(params, token, pages, block_tables, lengths):
            return T.decode_step_paged(params, token, pages, block_tables, lengths, cfg, pctx)

        in_sh = None
        if self.mesh is not None:
            psh = self.param_shardings(params_abstract)
            in_sh = (psh, self._sd(pctx, "batch"), self.page_pool_shardings(), None,
                     self._sd(pctx, "batch"))
        return self._program(key, fn, in_shardings=in_sh, donate=(2,),
                             phase="decode")

    def verify_program(self, params_abstract, batch: int, max_len: int, width: int) -> PhaseProgram:
        """The speculative VERIFY program over the contiguous cache:
        ``fn(params, tokens (B, W), cache, lengths, n_tokens) -> (logits
        (B, W, Vp), new_cache)`` (cache donated, in-place block append).

        A third decode-phase configuration next to ``decode``: the same
        bandwidth-optimized RM dataflow — stream the cache once — but
        scoring ``width = k + 1`` token positions per slot per round, so
        every accepted draft token amortizes the KV/weight stream the
        paper's Eq. (5) says decode is bound by.  One compiled shape per
        (slot batch, width); ``lengths``/``n_tokens`` are traced operands,
        so acceptance-dependent rollback never recompiles."""
        key = f"verify:{batch}x{width}@{max_len}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.decode_ctx
        assert cfg.family == "transformer", "speculative verify implemented for the transformer family"
        from repro.models import transformer as T

        def fn(params, tokens, cache, lengths, n_tokens):
            return T.verify(params, tokens, cache, lengths, n_tokens, cfg, pctx)

        in_sh = None
        if self.mesh is not None:
            psh = self.param_shardings(params_abstract)
            in_sh = (psh, self._sd(pctx, "batch", None), self.cache_shardings(batch, max_len),
                     self._sd(pctx, "batch"), self._sd(pctx, "batch"))
        return self._program(key, fn, in_shardings=in_sh, donate=(2,),
                             phase="decode")

    def paged_verify_program(self, params_abstract, n_slots: int, max_pages: int, width: int) -> PhaseProgram:
        """Speculative verify over the paged pool: ``fn(params, tokens
        (B, W), pages, block_tables, lengths, n_tokens) -> (logits
        (B, W, Vp), new_pages)`` (pool donated).  See ``verify_program``;
        pages shard like ``paged_decode_program``."""
        key = f"verify_paged:{n_slots}x{width}@{max_pages}"
        if key in self._programs:
            return self._programs[key]
        cfg, pctx = self.cfg, self.decode_ctx
        assert cfg.family == "transformer", "speculative verify implemented for the transformer family"
        from repro.models import transformer as T

        def fn(params, tokens, pages, block_tables, lengths, n_tokens):
            return T.verify_paged(params, tokens, pages, block_tables, lengths, n_tokens, cfg, pctx)

        in_sh = None
        if self.mesh is not None:
            psh = self.param_shardings(params_abstract)
            in_sh = (psh, self._sd(pctx, "batch", None), self.page_pool_shardings(), None,
                     self._sd(pctx, "batch"), self._sd(pctx, "batch"))
        return self._program(key, fn, in_shardings=in_sh, donate=(2,),
                             phase="decode")

    def block_sampler_program(self, batch: int, width: int) -> PhaseProgram:
        """Vectorized verify-target sampler: ``fn(logits (B, W, V), seeds,
        step0s, temps, top_ks, top_ps) -> (B, W) tokens``.  Block position
        ``i`` of slot ``b`` draws with ``fold_in(PRNGKey(seeds[b]),
        step0s[b] + i)`` — the exact key stream sequential decode uses, so
        the speculative accept rule preserves sampled streams bit-for-bit
        (see ``repro.core.sampling.sample_block_tokens``)."""
        key = f"block_sampler:{batch}x{width}"
        if key in self._programs:
            return self._programs[key]
        from repro.core.sampling import sample_block_tokens

        return self._program(key, sample_block_tokens, phase="sampler")

    def sampler_program(self, batch: int) -> PhaseProgram:
        """Vectorized per-slot token sampler — the decode epilogue program:
        ``fn(logits, seeds, steps, temps, top_ks, top_ps) -> tokens``.

        One compiled configuration per slot-batch size, like the other phase
        programs; it runs after the decode step's logits on device, so a
        sampled batch costs one extra dispatch, not a host round-trip per
        slot.  The PRNG key for slot ``i`` is
        ``fold_in(PRNGKey(seeds[i]), steps[i])`` — stateless, which is what
        keeps preemption replay deterministic under sampling."""
        key = f"sampler:{batch}"
        if key in self._programs:
            return self._programs[key]
        from repro.core.sampling import sample_tokens

        # No pinned in_shardings: the logits arrive however the decode
        # program's epilogue left them (vocab over the model axis under tp;
        # replicated otherwise), and a size-1 batch (the prefill first-token
        # path) cannot be partitioned anyway — GSPMD propagates from the
        # operands for this tiny program.
        return self._program(key, sample_tokens, phase="sampler")

    def page_write_program(self, seq: int, block_size: int) -> PhaseProgram:
        """The paged swap: scatter prefill-layout KV into allocated pages —
        ``fn(pages, kv, page_ids) -> new_pages`` (pages donated).  Plays the
        role ``relayout_program`` plays for the contiguous cache; its
        dispatch is what the latency-overlapped swap hides behind the
        prefill tail.  Under ``kv_dtype`` in {"int8", "int4"} the scatter is
        quantize-on-write: the fp prefill KV is packed (payload + scale
        planes) on its way into the pool and never stored at full width."""
        key = f"page_write:{seq}@{block_size}"
        if key in self._programs:
            return self._programs[key]
        from repro.layers.attention import KVCache, write_prefill_pages_q

        def fn(pages, kv, page_ids):
            return KVCache(
                write_prefill_pages_q(pages.k, kv.k, page_ids, block_size=block_size),
                write_prefill_pages_q(pages.v, kv.v, page_ids, block_size=block_size),
            )

        return self._program(key, fn, donate=(0,), phase="swap")

    def cache_shardings(self, batch: int, max_len: int):
        """Shardings of the decode-layout cache that the decode and verify
        programs take (None without a mesh)."""
        if self.mesh is None:
            return None
        if self.kv_dtype != "fp":
            from repro.models import transformer as T

            cache_abstract = jax.eval_shape(
                lambda: T.init_cache(self.cfg, batch, max_len, kv_dtype=self.kv_dtype))
        else:
            cache_abstract = jax.eval_shape(lambda: self.api.init_cache(self.cfg, batch, max_len))
        return self._cache_shardings(cache_abstract)

    def page_pool_shardings(self):
        """Shardings of the page pool that the paged decode and verify
        programs take (None without a mesh): pages shard over heads and
        head_dim; the page axis stays replicated, since any sequence's
        table may reference any page."""
        if self.mesh is None:
            return None
        from repro.layers.attention import KVCache

        pctx = self.decode_ctx
        page_sh = self._sd(pctx, None, "layers", "kv_heads", None, "head_dim")
        if self.kv_dtype != "fp":
            from repro.quant.kv_quant import QuantKV

            leaf_sh = QuantKV(page_sh, self._sd(pctx, None, "layers", "kv_heads", None))
        else:
            leaf_sh = page_sh
        return KVCache(leaf_sh, leaf_sh)

    def _cache_shardings(self, cache_abstract):
        """Decode-layout cache shardings: KV sequence over the model axis,
        recurrent/SSM states over channels."""
        pctx = self.decode_ctx

        from repro.layers.sharding import sanitize_named_sharding

        def rule(path, leaf):
            ns = _raw_rule(path, leaf)
            return sanitize_named_sharding(ns, leaf.shape) if ns is not None else None

        def _raw_rule(path, leaf):
            nd = leaf.ndim
            p = path.lower()
            if "mlstm" in p:  # (G, nm, B, H, dk[, dv])
                names = [None] * nd
                if nd >= 3:
                    names[2] = "batch"
                if nd >= 5:
                    names[-1] = "state"  # matrix memory dv over tp (long ctx)
                return self._sd(pctx, *names)
            if "slstm" in p:  # (G, B, H, hd)
                return self._sd(pctx, None, "batch", None, "state")
            if "scale" in p and nd == 4:  # (B, L, Hkv, S) quantized-KV scale plane
                return self._sd(pctx, "batch", "layers", "kv_heads", "kv_seq")
            if nd == 5:  # (B, L, Hkv, S, D) KV — decode layout, batch-leading
                return self._sd(pctx, "batch", "layers", "kv_heads", "kv_seq", "head_dim")
            if "conv" in p and nd == 4:  # (L, B, w-1, d_in)
                return self._sd(pctx, "layers", "batch", None, "state")
            if nd == 4:  # (L, B, d_in, N) hymba ssm state
                return self._sd(pctx, "layers", "batch", "state", None)
            if nd == 3:  # (L, B, conv) hymba conv state etc.
                return self._sd(pctx, "layers", "batch", None)
            return self._sd(pctx, *([None] * nd)) if nd else None

        from repro.common.tree import tree_map_with_path_names

        return tree_map_with_path_names(rule, cache_abstract)


def static_engine_decode_rules():
    """The static-accelerator baseline (TeLLMe-style): decode runs with the
    *prefill* configuration — no relayout, KV stays in prefill sharding, the
    decode program is compiled with the compromise layout.  Used by the
    fig6 benchmark to reproduce the paper's PD-Swap-vs-static comparison."""
    return PREFILL_RULES
