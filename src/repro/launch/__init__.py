"""Subsystem package: CLI entry points + shared argparse plumbing."""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["add_amm_attn_arg", "resolve_amm_apply_to", "use_compile_cache",
           "validate_amm_args", "validate_serve_flags"]

# fixed, in the checkout: the cache directory is part of every entry's key
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no path of its own.  Otherwise the cache goes to the fixed
    ``.jax_cache`` directory at the root of the checkout.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def validate_amm_args(ap, args) -> None:
    """Reject invalid (--mul, --wl, --vbl) combinations at parse time.

    Shared by the train and serve launchers so a bad spec fails with one
    clear message before any params are initialized or caches built —
    previously an out-of-range VBL surfaced minutes later as a shape
    error deep in the Booth decode (or, worse, quantized everything to
    zero and "worked").  Checks mirror the datapath's real envelope:

      * unknown multiplier family (``core.MULTIPLIERS`` registry),
      * word length: even (radix-4 Booth pairs bits), 4..16 when an
        approximate mode is on (the int32 dot-form envelope; wl > 16
        only exists on the exact host FIR path),
      * VBL: ``0 <= vbl < wl`` for the BBM families (nullifying every
        bit is no longer a multiplier); kulkarni/bam interpret the knob
        differently and only require it non-negative.
    """
    if args.amm == "off":
        return
    from ..core.multipliers import MULTIPLIERS
    if args.mul not in MULTIPLIERS:
        ap.error(f"unknown --mul {args.mul!r}; choose from "
                 f"{sorted(MULTIPLIERS)}")
    if args.wl % 2 or not 4 <= args.wl <= 16:
        ap.error(f"--wl {args.wl} out of range: the approximate datapath "
                 f"needs an even word length in [4, 16] (int32 dot-form "
                 f"envelope)")
    if args.vbl < 0:
        ap.error(f"--vbl {args.vbl} must be non-negative")
    if args.mul in ("booth", "bbm0", "bbm1") and args.vbl >= args.wl:
        ap.error(f"--vbl {args.vbl} >= --wl {args.wl}: nullifying every "
                 f"product bit leaves no multiplier; VBL must be < WL")


def validate_serve_flags(ap, args) -> None:
    """Reject ``--kv-codes`` combinations the code cache cannot serve.

    The int-code KV cache stores exactly the quantized representation the
    Booth attention lowering consumes, so it only exists when decode
    attention is amm-routed: mode="bitexact", a Booth-family --mul, and
    --amm-attn present.  Anything else would need a float cache anyway —
    fail at parse time instead of deep inside ``Scheduler.__init__``.
    """
    if not getattr(args, "kv_codes", False):
        return
    from ..kernels.ref import AMM_BOOTH_KINDS
    if args.amm != "bitexact":
        ap.error(f"--kv-codes stores Booth codes, which only the bitexact "
                 f"datapath consumes; got --amm {args.amm}")
    if args.mul not in AMM_BOOTH_KINDS:
        ap.error(f"--kv-codes needs a Booth-family --mul "
                 f"({sorted(AMM_BOOTH_KINDS)}); got --mul {args.mul!r}")
    if args.amm_attn is None:
        ap.error("--kv-codes caches the attention operands, so attention "
                 "must be amm-routed: pass --amm-attn (or --amm-attn attn)")


def add_amm_attn_arg(ap) -> None:
    """The shared ``--amm-attn`` flag (train and serve launchers).

    Bare flag -> apply_to="all" (MLPs + attention); ``--amm-attn attn``
    -> attention only.  Attention routing engages only for
    mode="bitexact" with a Booth-family mul — under mode="noise" the
    MLPs still route but attention stays exact (docs/attention.md);
    ``resolve_amm_apply_to`` rejects the combinations that would
    approximate nothing at all.
    """
    ap.add_argument("--amm-attn", nargs="?", const="all", default=None,
                    choices=["attn", "all"],
                    help="route the attention QK^T/PV products through the "
                         "approximate datapath too (bare flag: MLPs + "
                         "attention, apply_to='all'; '--amm-attn attn': "
                         "attention only).  Attention routing needs "
                         "--amm bitexact with a Booth-family --mul; under "
                         "--amm noise the MLPs still route but attention "
                         "stays exact (docs/attention.md)")


def resolve_amm_apply_to(ap, args) -> str:
    """Validate the (--amm, --mul, --amm-attn) combination -> apply_to.

    apply_to="attn" excludes the MLPs and only the bitexact Booth
    datapath has an attention lowering (``kernels.ref.AMM_BOOTH_KINDS``,
    the same registry ``AmmRuntime.attn_active`` consults), so any other
    combination would silently compute the whole model exactly while
    labeled amm — reject it at the CLI instead.
    """
    from ..kernels.ref import AMM_BOOTH_KINDS
    if args.amm_attn == "attn" and not (
            args.amm == "bitexact" and args.mul in AMM_BOOTH_KINDS):
        ap.error("--amm-attn attn routes *only* attention, which needs "
                 "--amm bitexact with a Booth-family --mul; this "
                 "combination would approximate nothing")
    return args.amm_attn or "mlp"
