"""Pallas kernel invariant checker: BlockSpecs, index maps, no fp KV in HBM.

The four kernel packages (decode / paged / prefill attention + tlmm) keep
three hand-maintained invariants that, until now, only review enforced:

1. **block divisibility** — every ``BlockSpec`` block shape divides the
   (already padded) operand dim it tiles: a non-dividing block silently
   reads OOB rows in interpret mode and corrupts tiles on hardware;
2. **index maps in bounds** — evaluated at every grid point (with the
   *concrete* scalar-prefetch operands — block tables included), each
   index map must produce block offsets inside the operand.  This is what
   actually pins the block-table walk: a table entry past the page pool,
   or a ``ti``-indexed map missing its clamp, fails here at the grid
   extremes;
3. **fp cache never exists in HBM** (PR 3) — the quantized variants'
   jaxprs must not allocate an fp32 intermediate as large as the
   dequantized KV cache: dequant happens per-tile in VMEM inside the
   kernel, never as a whole-cache materialization feeding it;
4. **TPU block tiling** — Mosaic refuses a block whose last two dims are
   not divisible by (8, 128) and not equal to the array's dims (rank 1: a
   multiple of 128 x the dtype's packing, or the whole array).  Interpret
   mode accepts such blocks, so only a compile for the chip found them.

Mechanism: ``pl.pallas_call`` is monkeypatched to a recorder that captures
(grid, specs, operands) and returns zeros of ``out_shape``; each op entry
point is then invoked **unjitted** (``fn.__wrapped__``) across a
serving-bucket-style case grid, so the ops' own padding/clamping runs for
real while no kernel body ever executes.  Invariant 3 traces the entry
point with ``jax.make_jaxpr`` (recorder still active) and scans every
equation's output avals.

Kernel findings are waivable by baseline only — there is no meaningful
source line to hang a pragma on for a (case x grid-point) violation.
"""
from __future__ import annotations

import dataclasses
import itertools
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis.common import Finding

PASS = "kernel"

# Serving-bucket-style lengths (ModelRunner.bucket: quantum-aligned then
# geometric), deliberately including non-bucket raw lengths so the ops'
# partial-final-block padding paths (clamp bk, right-pad) are exercised.
BUCKET_LENGTHS = (8, 16, 48, 100, 128)

MAX_GRID_POINTS = 8192  # full enumeration bound; larger grids use corners


@dataclasses.dataclass
class KernelCase:
    """One concrete invocation of an op entry point."""
    label: str
    args: tuple
    kwargs: Dict[str, Any]
    # fp32-materialization threshold in ELEMENTS: the dequantized size of
    # one KV operand (K or V).  None disables invariant 3 for the case.
    fp_elems: Optional[int] = None


@dataclasses.dataclass
class _Captured:
    grid: Tuple[int, ...]
    in_specs: List[Any]
    out_specs: List[Any]
    nsp: int
    operand_shapes: List[Tuple[int, ...]]
    scalars: List[Any]  # concrete np arrays (or None when traced)
    # (shape, dtype) of every array a spec tiles: block operands, then outputs
    tiled: List[Tuple[Tuple[int, ...], Any]] = dataclasses.field(default_factory=list)


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _recorder(captured: List[_Captured]):
    """A stand-in for ``pl.pallas_call`` that records and returns zeros."""
    import jax.numpy as jnp
    import numpy as np

    def fake_pallas_call(kernel, *, grid_spec=None, grid=None, in_specs=None,
                         out_specs=None, out_shape=None, **kw):
        if grid_spec is not None:
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            g = tuple(getattr(grid_spec, "grid", ()) or ())
            ins = _as_list(getattr(grid_spec, "in_specs", None))
            outs = _as_list(getattr(grid_spec, "out_specs", None))
        else:
            nsp = 0
            g = tuple(grid) if grid else ()
            ins = _as_list(in_specs)
            outs = _as_list(out_specs)
        shapes = _as_list(out_shape)

        def runner(*operands):
            scalars: List[Any] = []
            for x in operands[:nsp]:
                try:
                    scalars.append(np.asarray(x))
                except Exception:  # traced under make_jaxpr: no concrete value
                    scalars.append(None)
            captured.append(_Captured(
                grid=g, in_specs=ins, out_specs=outs, nsp=nsp,
                operand_shapes=[tuple(x.shape) for x in operands],
                scalars=scalars,
                tiled=[(tuple(x.shape), x.dtype) for x in operands[nsp:]]
                + [(tuple(s.shape), s.dtype) for s in shapes]))
            res = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return res if isinstance(out_shape, (list, tuple)) else res[0]

        return runner

    return fake_pallas_call


def _grid_points(grid: Tuple[int, ...]):
    total = 1
    for g in grid:
        total *= max(int(g), 1)
    if total <= MAX_GRID_POINTS:
        return itertools.product(*(range(int(g)) for g in grid))
    # corners only: every combination of first/last per dimension
    return itertools.product(*(sorted({0, int(g) - 1}) for g in grid))


def _block_shape(spec) -> Optional[Tuple]:
    return getattr(spec, "block_shape", None)


def _index_map(spec) -> Optional[Callable]:
    return getattr(spec, "index_map", None)


def tpu_tiling_ok(block: Sequence, shape: Sequence[int], dtype) -> bool:
    """Mosaic's block-tiling rule (squeezed ``None`` dims count as 1)."""
    import numpy as np

    dims = [1 if b is None else int(b) for b in block]
    if len(dims) == 1:
        tile = 128 * max(1, 4 // np.dtype(dtype).itemsize)
        return dims[0] == shape[0] or dims[0] % tile == 0
    return ((dims[-1] == shape[-1] or dims[-1] % 128 == 0)
            and (dims[-2] == shape[-2] or dims[-2] % 8 == 0))


def _check_captured(cap: _Captured, where: Tuple[str, int], label: str,
                    findings: List[Finding]) -> None:
    rel, line = where
    specs = list(cap.in_specs) + list(cap.out_specs)
    # operand order at call time: [scalar-prefetch...] + block operands;
    # in_specs describe the block operands only
    shapes = list(cap.operand_shapes[cap.nsp:])
    # out shapes are not operands; reconstruct bounds from the specs'
    # index maps against the in-shapes we do have, and from block shapes
    # for outs we only check divisibility against themselves at map time.
    n_in = len(cap.in_specs)
    for si, spec in enumerate(specs):
        block = _block_shape(spec)
        if block is None:
            continue
        operand_shape = shapes[si] if si < len(shapes) else None
        if si < n_in and operand_shape is not None:
            if len(block) != len(operand_shape):
                findings.append(Finding(
                    PASS, "kernel:block-rank", rel, line,
                    f"{label}: in_spec[{si}] block rank {len(block)} != "
                    f"operand rank {len(operand_shape)}"))
                continue
            for d, b in enumerate(block):
                if b is None:
                    continue
                if operand_shape[d] % int(b) != 0:
                    findings.append(Finding(
                        PASS, "kernel:block-divisibility", rel, line,
                        f"{label}: in_spec[{si}] block dim {d} = {b} does "
                        f"not divide operand dim {operand_shape[d]} — the "
                        f"op must pad before tiling"))
    for si, spec in enumerate(specs):
        block = _block_shape(spec)
        if block is None or si >= len(cap.tiled):
            continue
        shape, dtype = cap.tiled[si]
        if len(block) == len(shape) and not tpu_tiling_ok(block, shape, dtype):
            kind = f"in_spec[{si}]" if si < n_in else f"out_spec[{si - n_in}]"
            findings.append(Finding(
                PASS, "kernel:block-tiling", rel, line,
                f"{label}: {kind} block {tuple(block)} over array {shape} "
                f"breaks the TPU tiling rule: the last two block dims must "
                f"be divisible by (8, 128) or equal the array's dims"))
    # index-map bounds (needs concrete scalars; skipped under tracing)
    if any(s is None for s in cap.scalars):
        return
    for si, spec in enumerate(specs):
        block = _block_shape(spec)
        imap = _index_map(spec)
        if block is None or imap is None:
            continue
        operand_shape = shapes[si] if si < n_in and si < len(shapes) else None
        if operand_shape is None or len(block) != len(operand_shape):
            continue
        bad = 0
        for pt in _grid_points(cap.grid):
            try:
                idx = imap(*pt, *cap.scalars)
            except Exception as e:
                findings.append(Finding(
                    PASS, "kernel:index-map-error", rel, line,
                    f"{label}: in_spec[{si}] index map raised {e!r} at grid "
                    f"point {pt}"))
                break
            idx = tuple(int(v) for v in idx)
            for d, (b, i) in enumerate(zip(block, idx)):
                bsz = 1 if b is None else int(b)
                if i < 0 or (i + 1) * bsz > operand_shape[d]:
                    findings.append(Finding(
                        PASS, "kernel:index-oob", rel, line,
                        f"{label}: in_spec[{si}] index map at grid point "
                        f"{pt} selects block {idx} (dim {d}: block {i} x "
                        f"{bsz} exceeds operand dim {operand_shape[d]})"))
                    bad += 1
                    break
            if bad >= 3:  # one shape of failure is enough signal per spec
                break


def _scan_fp_alloc(jaxpr, threshold: int, where: Tuple[str, int], label: str,
                   findings: List[Finding]) -> None:
    import numpy as np

    rel, line = where

    def walk(jx) -> None:
        for eqn in jx.eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                shape = getattr(aval, "shape", None)
                dtype = getattr(aval, "dtype", None)
                if shape is None or dtype is None:
                    continue
                if str(dtype) == "float32" and \
                        int(np.prod(shape, dtype=np.int64)) >= threshold:
                    findings.append(Finding(
                        PASS, "kernel:fp-cache-alloc", rel, line,
                        f"{label}: {eqn.primitive.name} allocates fp32 "
                        f"{tuple(shape)} (>= dequantized KV size "
                        f"{threshold}) — the fp cache must never exist in "
                        f"HBM; dequant belongs in-kernel, per tile"))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(inner)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)


def _where(fn, root: Optional[Path]) -> Tuple[str, int]:
    code = getattr(fn, "__wrapped__", fn).__code__
    path = Path(code.co_filename)
    rel = path.as_posix()
    if root is not None:
        try:
            rel = path.relative_to(root).as_posix()
        except ValueError:
            pass
    return rel, code.co_firstlineno


def check_op(fn, cases: Sequence[KernelCase], *,
             root: Optional[Path] = None) -> List[Finding]:
    """Run every case against one op entry point, checking all three
    invariants.  ``fn`` may be jitted (its ``__wrapped__`` is used)."""
    import functools
    from unittest import mock

    import jax
    from jax.experimental import pallas as pl_mod

    findings: List[Finding] = []
    where = _where(fn, root)
    raw = getattr(fn, "__wrapped__", fn)
    for case in cases:
        captured: List[_Captured] = []
        fake = _recorder(captured)
        with mock.patch.object(pl_mod, "pallas_call", fake):
            try:
                raw(*case.args, **case.kwargs)
            except Exception as e:
                findings.append(Finding(
                    PASS, "kernel:eval-error", where[0], where[1],
                    f"{case.label}: entry point raised {e!r} during "
                    f"abstract evaluation"))
                continue
            for cap in captured:
                _check_captured(cap, where, case.label, findings)
            if case.fp_elems is not None:
                try:
                    jaxpr = jax.make_jaxpr(
                        functools.partial(raw, **case.kwargs))(*case.args)
                except Exception as e:
                    findings.append(Finding(
                        PASS, "kernel:eval-error", where[0], where[1],
                        f"{case.label}: make_jaxpr raised {e!r}"))
                    continue
                _scan_fp_alloc(jaxpr, case.fp_elems, where,
                               case.label, findings)
    return findings


# --------------------------------------------------------------- case grid --

def _attention_cases():
    """Cases for the four attention entry points over the bucket grid."""
    import jax.numpy as jnp
    import numpy as np

    hkv, g, d = 2, 2, 16
    rng = np.random.default_rng(0)

    def lens(b, s):
        # grid extremes: empty, single token, partial block, full cache
        base = [1, s, max(1, s // 2), max(1, s - 1)]
        return jnp.asarray((base * b)[:b], jnp.int32)

    decode, decode_q, paged, paged_q = [], [], [], []
    for s in BUCKET_LENGTHS:
        for b in (1, 3):
            q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), jnp.float32)
            k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
            ln = lens(b, s)
            bk = 32  # forces multi-step KV walks and the partial-final pad
            decode.append(KernelCase(
                f"decode b={b} s={s} bk={bk}", (q, k, v, ln), {"bk": bk}))
            for kv_dtype in ("int8", "int4"):
                dp = d if kv_dtype == "int8" else d // 2
                kq = jnp.zeros((b, hkv, s, dp), jnp.int8)
                ks = jnp.ones((b, hkv, s), jnp.float32)
                # invariant 3 only engages once the cache dwarfs the
                # per-query intermediates (the l/m stats are (g, 128) f32
                # per head — legitimate, and bigger than a toy cache)
                fp = b * hkv * s * d if s * d >= 2 * g * 128 else None
                decode_q.append(KernelCase(
                    f"decode-quant {kv_dtype} b={b} s={s} bk={bk}",
                    (q, kq, ks, kq, ks, ln),
                    {"kv_dtype": kv_dtype, "bk": bk},
                    fp_elems=fp))

    # paged: pool of n pages; tables exercise id 0, id n-1 and repeats
    bs, n = 16, 8
    for n_pages in (1, 3):
        for b in (1, 3):
            q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), jnp.float32)
            kp = jnp.asarray(
                rng.standard_normal((n, hkv, bs, d)), jnp.float32)
            tbl = jnp.asarray(
                rng.integers(0, n, (b, n_pages)), jnp.int32)
            tbl = tbl.at[0, 0].set(0)
            tbl = tbl.at[-1, -1].set(n - 1)
            ln = lens(b, n_pages * bs)
            paged.append(KernelCase(
                f"paged b={b} pages={n_pages}", (q, kp, kp, tbl, ln), {}))
            for kv_dtype in ("int8", "int4"):
                dp = d if kv_dtype == "int8" else d // 2
                kpq = jnp.zeros((n, hkv, bs, dp), jnp.int8)
                kps = jnp.ones((n, hkv, bs), jnp.float32)
                paged_q.append(KernelCase(
                    f"paged-quant {kv_dtype} b={b} pages={n_pages}",
                    (q, kpq, kps, kpq, kps, tbl, ln),
                    {"kv_dtype": kv_dtype},
                    fp_elems=n * hkv * bs * d))
    return decode, decode_q, paged, paged_q


def _prefill_cases():
    import jax.numpy as jnp
    import numpy as np

    h, hkv, d = 4, 2, 16
    rng = np.random.default_rng(1)
    cases = []
    for s, blk in ((64, 32), (128, 32), (128, 64)):
        for schedule in ("reverse", "forward"):
            q = jnp.asarray(rng.standard_normal((1, h, s, d)), jnp.float32)
            k = jnp.asarray(rng.standard_normal((1, hkv, s, d)), jnp.float32)
            cases.append(KernelCase(
                f"prefill s={s} blk={blk} {schedule}", (q, k, k),
                {"blk": blk, "schedule": schedule}))
    return cases


def _tlmm_cases():
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(2)
    cases = []
    for (m, n, k, bm, bn, bk) in ((128, 128, 512, 128, 128, 512),
                                  (256, 256, 1024, 128, 128, 256)):
        xq = jnp.asarray(rng.integers(-8, 8, (m, k)), jnp.int8)
        wp = jnp.asarray(rng.integers(0, 255, (k // 4, n)), jnp.uint8)
        sc = jnp.ones((m, 1), jnp.float32)
        cases.append(KernelCase(
            f"tlmm m={m} n={n} k={k} bm={bm} bn={bn} bk={bk}",
            (xq, wp, sc), {"bm": bm, "bn": bn, "bk": bk}))
    return cases


def run(root: Path, subset: Optional[Sequence[str]] = None) -> List[Finding]:
    """Check all kernel packages.  ``root`` is used only to relativize
    reported paths (the ops under test are the imported ones)."""
    from repro.kernels.decode_attention.kernel import (
        decode_attention_pallas, decode_attention_quant_pallas)
    from repro.kernels.paged_attention.kernel import (
        paged_decode_attention_pallas, paged_decode_attention_quant_pallas)
    from repro.kernels.prefill_attention.kernel import prefill_attention_pallas
    from repro.kernels.tlmm.kernel import tlmm_pallas

    decode, decode_q, paged, paged_q = _attention_cases()
    findings: List[Finding] = []
    findings += check_op(decode_attention_pallas, decode, root=root)
    findings += check_op(decode_attention_quant_pallas, decode_q, root=root)
    findings += check_op(paged_decode_attention_pallas, paged, root=root)
    findings += check_op(
        paged_decode_attention_quant_pallas, paged_q, root=root)
    findings += check_op(prefill_attention_pallas, _prefill_cases(), root=root)
    findings += check_op(tlmm_pallas, _tlmm_cases(), root=root)
    return findings
