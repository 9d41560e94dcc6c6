"""Layer 3 — Mosaic kernel audit: no hardware, two complementary checks.

**Cross-platform lowering** (HL201): each kernel in
:mod:`harp_tpu.ops.kernel_registry` is traced and lowered with
``lowering_platforms=("tpu",)`` on the CPU backend — the full
Pallas→Mosaic pass (block-shape rules, missing primitives, unsupported
casts) that caught three kernels the chip would have refused, on
2026-07-31, without a chip.

**Silicon-limit jaxpr checks** (HL202/HL203/HL204): the REAL toolchain
enforces rules the local Mosaic pass does not — ``pltpu.prng_seed``
accepts at most TWO seed words on silicon (the 2026-08-01 failure: 3
words lowered fine locally, failed the compile on the chip), Mosaic
has no uint32→f32 cast, and block dim −2 must be a multiple of 8 or the
full array dim.  These are checked by walking the traced jaxpr's
``pallas_call`` eqns directly, so they fire even where local lowering
stays green.

Both run over the same trace, so one registry sweep audits everything.
"""

from __future__ import annotations

from typing import Any

from harp_tpu.analysis import Violation

_MAX_PRNG_SEED_WORDS = 2  # silicon limit, 2026-08-01


def _walk_jaxprs(jaxpr):
    """Yield (eqn, enclosing_jaxpr) for every eqn at any nesting depth."""
    for eqn in jaxpr.eqns:
        yield eqn, jaxpr
        for v in eqn.params.values():
            core = getattr(v, "jaxpr", None)
            if core is not None and hasattr(core, "eqns"):
                yield from _walk_jaxprs(core)
            elif hasattr(v, "eqns"):
                yield from _walk_jaxprs(v)


def _block_shape(bm) -> tuple:
    # jax 0.9.0 block dims are pallas ``Blocked(block_size=n)`` objects
    # (``Squeezed`` dims have no size and stay None)
    return tuple(getattr(d, "block_size", None) for d in bm.block_shape)


def check_kernel_jaxpr(closed_jaxpr, target: str) -> list[Violation]:
    """HL202/HL203/HL204 over one traced program's pallas_call eqns."""
    out: list[Violation] = []
    for eqn, _ in _walk_jaxprs(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "prng_seed" and len(eqn.invars) > _MAX_PRNG_SEED_WORDS:
            out.append(Violation(
                "HL202", target, 0,
                f"pltpu.prng_seed called with {len(eqn.invars)} seed "
                f"words — the real TPU toolchain accepts at most "
                f"{_MAX_PRNG_SEED_WORDS} ('Setting seed with more than 2 "
                "values is not supported', silicon 2026-08-01); fold "
                "extra stream ids into a word with an odd-constant "
                "multiply + xor"))
        if name == "convert_element_type":
            import jax.numpy as jnp

            src = getattr(eqn.invars[0], "aval", None)
            dst = eqn.params.get("new_dtype")
            if (src is not None and dst is not None
                    and jnp.dtype(src.dtype) == jnp.dtype(jnp.uint32)
                    and jnp.issubdtype(jnp.dtype(dst), jnp.floating)):
                out.append(Violation(
                    "HL203", target, 0,
                    "uint32→float cast — Mosaic has no such lowering on "
                    "TPU; shift_right_logical on int32 instead (see "
                    "ops/lda_kernel.py's prng-bits→uniform idiom)"))
        if name == "pallas_call":
            out.extend(_check_block_shapes(eqn, target))
    return out


def _check_block_shapes(eqn, target: str) -> list[Violation]:
    out: list[Violation] = []
    for bm in eqn.params["grid_mapping"].block_mappings:
        bs = _block_shape(bm)
        if len(bs) < 2 or bs[-2] is None:
            continue
        full = bm.array_aval.shape[-2]
        if bs[-2] % 8 != 0 and bs[-2] != full:
            origin = bm.origin
            out.append(Violation(
                "HL204", target, 0,
                f"pallas block_shape {bs} for {origin}: dim -2 = "
                f"{bs[-2]} is neither a multiple of 8 (sublanes) nor "
                f"the full array dim ({full}) — fails the real Mosaic "
                "layout rules"))
    return out


def audit_kernel(name: str, fn, args) -> list[Violation]:
    """Trace + silicon checks + full Mosaic lowering for one kernel."""
    import jax

    target = f"kernel:{name}"
    try:
        traced = jax.jit(fn).trace(*args)
    except Exception as e:  # noqa: BLE001 - any trace failure is a finding
        return [Violation("HL201", target, 0,
                          f"kernel failed to trace: {type(e).__name__}: "
                          f"{e}")]
    out = check_kernel_jaxpr(traced.jaxpr, target)
    try:
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        if "tpu_custom_call" not in text:
            out.append(Violation(
                "HL201", target, 0,
                "lowered program contains no tpu_custom_call — the "
                "Pallas kernel fell out of the compiled path (interpret "
                "mode leaked in?)"))
    except Exception as e:  # noqa: BLE001
        out.append(Violation(
            "HL201", target, 0,
            f"Pallas→Mosaic lowering failed on the CPU backend: "
            f"{type(e).__name__}: {e}"))
    return out


def _declared_vmem_models() -> dict[str, int]:
    """Kernel-name → the kernel's OWN byte model evaluated at the
    registry's registered shape — the cross-check source for HL205.

    Only kernels exposing an analytic scoped-VMEM function participate;
    shapes mirror the registry builders' comments (a registry shape
    change must update BOTH or the audit fires, which is the point)."""
    from harp_tpu.ops import (kmeans_kernel, rf_kernel, svm_kernel,
                              wdamds_kernel)

    return {
        # tn=128, d=256, kp=128 (kmeans.partials_int8 builder shape)
        "kmeans.partials_int8": kmeans_kernel.vmem_bytes_int8(128, 256,
                                                              128),
        # dp=128, tn=128, xsize=4 (f32 operand)
        "svm.kernel_row": svm_kernel.vmem_bytes(128, 128, 4),
        # dimp=128, N=256, tn=32, dsize=4
        "wdamds.smacof_dist": wdamds_kernel.vmem_bytes(128, 256, 32, 4),
        # tn=128, fB=512, nodeCp=8
        "rf.hist_bins": rf_kernel.vmem_bytes(128, 512, 8),
    }


def check_work_declarations() -> list[Violation]:
    """HL205 — registry ``vmem_bytes`` declarations vs the kernels' own
    byte models.  A declaration must sit within ``memrec.PRESIZE_BAND``
    of the model at the registered shape (stale = mis-priced sprints
    AND a lying memrec VMEM gate) and under the 16 MB/core ceiling."""
    from harp_tpu.ops.kernel_registry import KERNEL_WORK
    from harp_tpu.utils import memrec

    out: list[Violation] = []
    for name, model in sorted(_declared_vmem_models().items()):
        work = KERNEL_WORK.get(name)
        if work is None:
            out.append(Violation(
                "HL205", f"kernel:{name}", 0,
                "kernel has an analytic VMEM byte model but no registry "
                "entry — register it (kernel_registry.py) so the audit "
                "and the perfmodel see one source of truth"))
            continue
        declared = work["vmem_bytes"]
        if not model <= declared <= model * memrec.PRESIZE_BAND:
            out.append(Violation(
                "HL205", f"kernel:{name}", 0,
                f"registry vmem_bytes={declared} is stale against the "
                f"kernel's own byte model ({model} B at the registered "
                f"shape; allowed band [{model}, "
                f"{int(model * memrec.PRESIZE_BAND)}]) — re-derive the "
                "declaration (perfmodel.presize) when the kernel "
                "changes"))
        if declared > memrec.VMEM_CEILING:
            out.append(Violation(
                "HL205", f"kernel:{name}", 0,
                f"registry vmem_bytes={declared} exceeds the "
                f"{memrec.VMEM_CEILING >> 20} MB/core VMEM ceiling — "
                "the registered shape itself cannot launch"))
    return out


def audit_registry(names: list[str] | None = None) -> list[Violation]:
    """Audit every registered kernel (or the named subset).  A full
    sweep (names=None) also cross-checks the registry work declarations
    against the kernels' own byte models (HL205)."""
    from harp_tpu.ops.kernel_registry import KERNELS

    out: list[Violation] = []
    for name in sorted(KERNELS if names is None else names):
        try:
            fn, args = KERNELS[name]()
        except Exception as e:  # noqa: BLE001 - a broken builder is loud
            out.append(Violation("HL201", f"kernel:{name}", 0,
                                 f"kernel builder failed: "
                                 f"{type(e).__name__}: {e}"))
            continue
        out.extend(audit_kernel(name, fn, args))
    if names is None:
        out.extend(check_work_declarations())
    return out


def registered_kernels() -> list[str]:
    from harp_tpu.ops.kernel_registry import KERNELS

    return sorted(KERNELS)
