"""Selective activation rematerialization — named policies over tagged
activations.

The reference ships activation checkpointing as an all-or-nothing wrapper
(``reference:apex/transformer/tensor_parallel/memory.py`` +
``random.py:checkpoint`` — the RNG-replaying checkpointed forward), and the
first port mirrored that bluntness: a ``remat: bool`` that wrapped the whole
layer in ``jax.checkpoint`` with the default save-nothing policy, recomputing
every GEMM *and* the flash-attention kernel in the backward. Megatron-style
*selective* recomputation (Korthikanti et al., "Reducing Activation
Recomputation in Large Transformer Models") shows most of the memory win
comes from dropping only the cheap-to-recompute activations (LayerNorms,
gelu, residual adds, reshapes) while keeping GEMM and attention-kernel
outputs resident — recovering the ~30% of backward FLOPs full remat burns.

This module is the single source of truth for that knob:

- :data:`CHECKPOINT_NAMES` — the central registry of
  ``jax.ad_checkpoint.checkpoint_name`` tags the models emit. Every tag
  literal in the package MUST come from this tuple
  (``scripts/check_remat_names.py`` enforces it statically): an orphan tag
  is an activation no policy can address, and a policy naming a tag nobody
  emits silently saves nothing.
- :func:`tag` — the tagging chokepoint (validates against the registry at
  trace time).
- :class:`RematPolicy` — ``none | full | selective | offload`` (plus a
  custom ``names`` save-list), mapping onto ``jax.checkpoint`` with
  ``jax.checkpoint_policies.save_only_these_names`` /
  ``save_and_offload_only_these_names``. ``full`` is *exactly* the old
  ``remat=True`` program (plain ``jax.checkpoint``, no tags — models gate
  their tag calls on :attr:`RematPolicy.uses_names`, so the ``full`` and
  ``none`` jaxprs carry zero ``name`` equations and stay identical to the
  pre-policy programs; asserted in ``tests/test_remat_policy.py``).

Flash attention under ``selective``: saving the kernel's *output* alone
would not keep the kernel out of the recomputed set — its ``custom_vjp``
backward also needs the logsumexp residual, and an unsaved residual forces
the forward kernel to rerun inside the remat region. The kernel therefore
tags both its context output (``flash_ctx``) and its logsumexp
(``flash_lse``) inside the custom_vjp *forward rule* (where residuals are
traced under AD), so ``save_only_these_names`` keeps everything the
backward kernel needs resident and DCE drops the forward kernel from the
recompute entirely (asserted structurally on the jaxpr).

Determinism under recompute: both dropout streams are counter-based — the
in-kernel flash dropout regenerates its keep mask from the packed seed, and
hidden dropout draws from explicit ``jax.random`` keys — so a recomputed
forward reproduces bit-identical masks under every policy (no torch-style
RNG-state save/restore needed; ``tensor_parallel/random.py``'s
``CheckpointFunction`` fork/restore machinery has no analog here).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

__all__ = ["CHECKPOINT_NAMES", "SELECTIVE_SAVE", "RematPolicy", "tag"]

# The central registry: every checkpoint_name tag the models emit. Keep
# entries as plain string literals — scripts/check_remat_names.py parses
# this tuple from the AST (no jax import) and cross-checks every tag call
# site in the package against it.
CHECKPOINT_NAMES: Tuple[str, ...] = (
    "flash_ctx",       # flash-attention context (kernel output)
    "flash_lse",       # flash-attention logsumexp (custom_vjp residual)
    "qkv_out",         # fused QKV ColumnParallel GEMM output
    "attn_proj_out",   # attention RowParallel projection GEMM output
    "mlp_fc1_out",     # MLP up-projection GEMM output (pre-gelu)
    "mlp_fc2_out",     # MLP down-projection GEMM output
    "ln_out",          # LayerNorm outputs (ln1 / ln2 / final)
)

# Megatron-selective default save-list: GEMM and flash outputs stay
# resident (each costs one GEMM / one kernel launch to recompute); LN
# outputs are dropped (one fused elementwise pass to recompute, the cheap
# trade the selective mode exists for).
SELECTIVE_SAVE: Tuple[str, ...] = (
    "flash_ctx",
    "flash_lse",
    "qkv_out",
    "attn_proj_out",
    "mlp_fc1_out",
    "mlp_fc2_out",
)

_MODES = ("none", "full", "selective", "offload")


def tag(x, name: str):
    """``jax.ad_checkpoint.checkpoint_name`` through the registry: tags
    ``x`` so a name-based :class:`RematPolicy` can save/offload it. A name
    outside :data:`CHECKPOINT_NAMES` raises — an unregistered tag is an
    activation the policies silently miss."""
    if name not in CHECKPOINT_NAMES:
        raise ValueError(
            f"checkpoint name {name!r} is not in remat.CHECKPOINT_NAMES; "
            f"register it there (and in the selective save-list if it "
            f"should stay resident) — orphan tags are unreachable by "
            f"every policy")
    return _checkpoint_name(x, name)


@dataclasses.dataclass(frozen=True)
class RematPolicy:
    """Activation-checkpoint policy for a layer/stage function.

    ``mode``:

    - ``"none"`` — no checkpointing (AD saves every residual; the GPT
      MLP's tanh-GELU, ``ops.mlp.gelu_tanh``, saves its input alone and
      recomputes the rest of its derivative in the backward);
    - ``"full"`` — plain ``jax.checkpoint`` with the default save-nothing
      policy: the pre-policy ``remat=True`` program, jaxpr-identical;
    - ``"selective"`` — ``save_only_these_names(*save_names)``: registry-
      tagged GEMM/flash outputs stay resident, everything else (LNs, gelu,
      adds) is recomputed;
    - ``"offload"`` — ``save_and_offload_only_these_names``: the same
      tagged set is offloaded to ``offload_dst`` (default pinned host
      memory) during forward and fetched back for backward — HBM cost of
      ``full`` with the recompute cost of ``selective``, paid in
      host-interconnect bandwidth.

    ``names``: custom save/offload list (must be registry members);
    ``None`` selects :data:`SELECTIVE_SAVE`. Only meaningful for the
    name-based modes.
    """

    mode: str = "none"
    names: Optional[Tuple[str, ...]] = None
    offload_src: str = "device"
    offload_dst: str = "pinned_host"

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(
                f"remat mode {self.mode!r}; expected one of {_MODES}")
        if self.names is not None:
            object.__setattr__(self, "names", tuple(self.names))
            if self.mode not in ("selective", "offload"):
                raise ValueError(
                    f"names={self.names!r} is only meaningful for "
                    f"selective/offload policies, not mode={self.mode!r}")
            unknown = [n for n in self.names if n not in CHECKPOINT_NAMES]
            if unknown:
                raise ValueError(
                    f"unregistered checkpoint names {unknown}; the "
                    f"registry is remat.CHECKPOINT_NAMES={CHECKPOINT_NAMES}")

    # -- derived ----------------------------------------------------------
    @property
    def uses_names(self) -> bool:
        """Whether this policy consumes ``checkpoint_name`` tags — models
        gate their tag emission on this so ``none``/``full`` programs stay
        byte-identical to the pre-policy ones."""
        return self.mode in ("selective", "offload")

    @property
    def save_names(self) -> Tuple[str, ...]:
        return self.names if self.names is not None else SELECTIVE_SAVE

    # -- application ------------------------------------------------------
    def wrap(self, fn: Callable) -> Callable:
        """The ``jax.checkpoint`` wrapper this policy denotes (identity
        for ``none``)."""
        if self.mode == "none":
            return fn
        if self.mode == "full":
            # exactly the legacy remat=True spelling — no policy kwarg, so
            # the traced program cannot drift from the pre-policy one
            return jax.checkpoint(fn)
        if self.mode == "selective":
            policy = jax.checkpoint_policies.save_only_these_names(
                *self.save_names)
        else:  # offload
            policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(self.save_names),
                offload_src=self.offload_src,
                offload_dst=self.offload_dst)
        return jax.checkpoint(fn, policy=policy)

    # -- construction -----------------------------------------------------
    @classmethod
    def resolve(cls, value: Any = None, legacy_bool: Optional[bool] = None,
                owner: str = "config") -> "RematPolicy":
        """Normalize every accepted spelling to a policy object.

        ``value``: ``None`` | mode string | bool | :class:`RematPolicy`.
        ``legacy_bool``: the deprecated ``remat: bool`` config field,
        consulted only when ``value`` is None — ``True`` maps to ``full``
        with a :class:`DeprecationWarning` (the config round-trip keeps
        working; new code should set ``remat_policy``). A bool passed as
        ``value`` (the pipeline schedules' ``remat`` flag) maps silently —
        that flag predates the policies and stays a supported API.
        """
        if isinstance(value, cls):
            return value
        if value is None:
            if legacy_bool:
                warnings.warn(
                    f"{owner}.remat=True (bool) is deprecated; use "
                    f"remat_policy='full' (or 'selective'/'offload' for "
                    f"the cheaper name-based policies)",
                    DeprecationWarning, stacklevel=3)
                return cls(mode="full")
            return cls(mode="none")
        if isinstance(value, bool):
            return cls(mode="full" if value else "none")
        if isinstance(value, str):
            return cls(mode=value)
        raise TypeError(
            f"cannot resolve a remat policy from {value!r} "
            f"(expected None, bool, mode string, or RematPolicy)")
