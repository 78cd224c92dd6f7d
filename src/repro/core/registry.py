"""Dialect-aware lowering registry + execution policy (the Table V dispatch).

The paper's central mechanism is that programs never hardcode vendor
parameters — they query a dialect and the runtime picks the legal lowering.
This module is that mechanism as a subsystem:

- Every kernel variant registers a :class:`Lowering` —
  ``(op, IsaMode, KernelContract, structural_cost, impl)`` — and is
  contract-checked **at registration time** (a variant whose primitive
  budget is out of contract cannot even be installed).
- An :class:`ExecutionPolicy` (dialect, mode preference or ``"auto"``,
  interpret flag) is resolved once per model/run and threaded through the
  layers above the kernels; every norm/attention/reduce hot spot routes
  through :meth:`LoweringRegistry.select` instead of per-call-site mode
  strings.
- ``"auto"`` selects the cheapest registered variant whose contract is
  legal for the active dialect, ranked by the kernel's own
  ``structural_cost`` model (scratch traffic first — the §VII.C currency),
  falling back to the jnp ``library`` reference only when no Pallas
  lowering is legal (e.g. a shuffle-only op on a ``has_lane_shuffle=False``
  dialect).
- Unsupported modes are handled by **declared fallbacks** (e.g. GEMM has
  no shuffle variant: the MXU contraction *is* its cross-lane stage), which
  warn and are recorded in :attr:`LoweringRegistry.fallback_events` — never
  by silent rewrites.

Native lowerings are *pinned* to the dialect they were built against
(their ``native_features`` are that target's feature set), so under a
foreign dialect only the portable budgets compete — the paper's Table V
discipline as runtime behavior.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import inspect
import warnings
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax

from repro.core.dialect import Dialect, TARGET, get_dialect
from repro.core.primitives import (ContractViolation, IsaMode,
                                   KernelContract, validate_contract)

#: the mode strings a policy may request, beyond the IsaMode values
AUTO = "auto"
POLICY_MODES = tuple(m.value for m in IsaMode) + (AUTO,)

#: weight-precision knobs a policy may carry.  ``None`` and ``"f32"`` both
#: mean full precision; other values retarget ops that registered a
#: precision variant (ISSUE 7: ``"int8"`` — per-channel-scaled weights
#: dequantized in VMEM).
POLICY_PRECISIONS = (None, "f32", "int8")

#: stable cheapness tiebreak: smaller primitive budget wins a cost tie,
#: the library escape hatch never wins one.
_PORTABILITY = {IsaMode.ABSTRACT: 0, IsaMode.ABSTRACT_SHUFFLE: 1,
                IsaMode.NATIVE: 2, IsaMode.LIBRARY: 3}


class UnsupportedLowering(RuntimeError):
    """Requested a lowering the registry cannot legally provide."""


class LoweringFallbackWarning(UserWarning):
    """A declared fallback (or the auto library escape) was taken."""


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How the layers above the kernels want their hot spots lowered.

    ``mode`` is an :class:`IsaMode` value or ``"auto"``; ``dialect`` names
    the target whose legality rules apply; ``interpret`` overrides the
    Pallas interpret default (None = backend-derived).  ``kernel_mode``
    optionally overrides ``mode`` for explicitly kernel-routed paths (the
    ``use_pallas_attn`` flash hot spot keeps its target-native variant
    while model norms default to the XLA library lowering).

    ``fuse`` gates the multi-op fused lowerings (``rmsnorm_matmul`` /
    ``add_rmsnorm``) at the model hot pairs: ``True`` routes the pairs
    through the fused ops, ``False`` keeps the unfused sequence, and
    ``None`` (default) fuses exactly when ``mode == "auto"`` — the policy
    that ranks lowerings by structural cost is the one that should pick
    the variant whose ``hbm_bytes`` dropped by an activation round trip.

    ``precision`` treats weight precision as one more dialect parameter
    (ISSUE 7): ``"int8"`` retargets every op that registered a precision
    variant (:meth:`LoweringRegistry.register_precision_variant`) to its
    quantized twin at the :meth:`LoweringRegistry.select` dispatch point,
    wherever the dialect keeps that variant legal; ops without a variant
    are untouched (the declared-fallback discipline, not an error).
    """

    mode: str = AUTO
    dialect: str = TARGET.name
    interpret: Optional[bool] = None
    kernel_mode: Optional[str] = None
    fuse: Optional[bool] = None
    precision: Optional[str] = None

    def __post_init__(self):
        for m in (self.mode, self.kernel_mode):
            if m is not None and m not in POLICY_MODES:
                raise ValueError(
                    f"unknown isa mode {m!r}; valid: {POLICY_MODES}")
        if self.precision not in POLICY_PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; valid: "
                f"{POLICY_PRECISIONS}")

    def resolved_dialect(self) -> Dialect:
        return get_dialect(self.dialect)

    def kernel(self) -> "ExecutionPolicy":
        """The policy for kernel-routed hot spots (flash attention)."""
        if self.kernel_mode is None or self.kernel_mode == self.mode:
            return self
        return dataclasses.replace(self, mode=self.kernel_mode,
                                   kernel_mode=None)

    def fuses(self) -> bool:
        """Whether model hot pairs route through the fused lowerings."""
        if self.fuse is not None:
            return self.fuse
        return self.mode == AUTO


#: seed-equivalent defaults: bare kernel-API calls keep the target-native
#: variant; model-level norms keep the XLA library lowering.
DEFAULT_POLICY = ExecutionPolicy(mode=IsaMode.NATIVE.value)
LIBRARY_POLICY = ExecutionPolicy(mode=IsaMode.LIBRARY.value)
AUTO_POLICY = ExecutionPolicy(mode=AUTO)

_policy_var: contextvars.ContextVar[Optional[ExecutionPolicy]] = \
    contextvars.ContextVar("uisa_execution_policy", default=None)

#: ambient mesh axes installed by :func:`use_mesh_axes` — the axis-name ->
#: size mapping the collective cost terms resolve their group size from
_mesh_axes_var: contextvars.ContextVar[Optional[Mapping[str, int]]] = \
    contextvars.ContextVar("uisa_mesh_axes", default=None)

#: the tensor-parallel mesh axis the collective twins shard over
TP_AXIS = "model"


@contextlib.contextmanager
def use_mesh_axes(axes: Mapping[str, int]):
    """Install ``axes`` (axis name -> size) as the ambient mesh for the
    dynamic extent.  This is the planner-side mirror of a ``jax.Mesh``
    context: selection and cost modeling read axis sizes from here first,
    so mesh-sensitive ranking can run without constructing devices."""
    token = _mesh_axes_var.set(dict(axes))
    try:
        yield axes
    finally:
        _mesh_axes_var.reset(token)


def ambient_mesh_axes() -> Dict[str, int]:
    """The ambient mesh axis sizes: :func:`use_mesh_axes` first, else the
    mesh entered with ``jax.set_mesh`` (also visible while tracing under
    it), else empty."""
    axes = _mesh_axes_var.get()
    if axes is not None:
        return dict(axes)
    return dict(jax.sharding.get_abstract_mesh().shape)


def tp_axis_size(axis: str = TP_AXIS) -> int:
    """Size of the tensor-parallel axis in the ambient mesh (1 = no TP)."""
    return int(ambient_mesh_axes().get(axis, 1))


def current_policy() -> Optional[ExecutionPolicy]:
    """The ambient policy installed by :func:`use_policy`, if any."""
    return _policy_var.get()


@contextlib.contextmanager
def use_policy(policy: ExecutionPolicy):
    """Install ``policy`` as the ambient default for the dynamic extent.

    Read at *trace* time: code already jitted under a different policy
    keeps its traced lowering (policies are resolved once, not per call).
    """
    token = _policy_var.set(policy)
    try:
        yield policy
    finally:
        _policy_var.reset(token)


def resolve_policy(mode=None, policy: Optional[ExecutionPolicy] = None,
                   default: ExecutionPolicy = DEFAULT_POLICY
                   ) -> ExecutionPolicy:
    """One resolution point: explicit mode > explicit policy > ambient >
    ``default``.  A ``mode`` override keeps the rest of the resolved
    policy (dialect, interpret) — the legality check must still run
    against the caller's dialect, not silently revert to the target."""
    base = policy or current_policy() or default
    if mode is not None:
        if isinstance(mode, IsaMode):
            mode = mode.value
        return dataclasses.replace(base, mode=mode, kernel_mode=None)
    return base


@dataclasses.dataclass(frozen=True)
class Lowering:
    """One registered realization of an abstract op."""

    op: str
    mode: IsaMode
    impl: Callable
    contract: KernelContract
    cost: Optional[Callable[..., Mapping]] = None
    #: dialect a native lowering is pinned to (its native_features are that
    #: target's feature set); portable lowerings carry None
    target: Optional[str] = None

    def structural_cost(self, plan_dialect: Optional[str] = None,
                        **shape) -> Mapping:
        """Modeled cost at ``shape``; ``plan_dialect`` names the tuning-
        table slice the model consults (None = ambient, then TARGET)."""
        if self.cost is None:
            return {}
        if plan_dialect is None:
            return self.cost(**shape)
        return self.cost(plan_dialect=plan_dialect, **shape)


@dataclasses.dataclass(frozen=True)
class Fallback:
    op: str
    missing: IsaMode
    to: IsaMode
    reason: str


@dataclasses.dataclass(frozen=True)
class FallbackEvent:
    op: str
    requested: str
    used: str
    reason: str


def cost_key(cost: Mapping, mode: IsaMode) -> Tuple:
    """Cheapness ranking for auto selection.

    Scratch traffic is the §VII.C currency, round trips its latency proxy,
    HBM bytes the bandwidth term; the primitive-budget rank breaks ties in
    favor of the more portable variant (so abstract+shuffle beats native
    when both model to zero scratch).  Collective traffic (ISSUE 10) is
    folded into the bandwidth term pre-converted to HBM-equivalent bytes
    (wire bytes x hbm_bw/link_bw + hop latency x hbm_bw), so a TP-fused
    variant's saved weight streams compete directly against the
    all-reduce it pays."""
    return (cost.get("scratch_bytes_total", 0),
            cost.get("scratch_round_trips_per_block", 0),
            cost.get("hbm_bytes", 0)
            + cost.get("collective_hbm_equiv_bytes", 0),
            _PORTABILITY[mode])


class LoweringRegistry:
    """op -> {IsaMode -> Lowering}, plus declared fallbacks + event log."""

    #: retained fallback events — bounded so a long-lived serving process
    #: whose policy takes a fallback on every retrace cannot grow it
    EVENT_LOG_MAXLEN = 256

    def __init__(self):
        self._variants: Dict[str, Dict[IsaMode, Lowering]] = {}
        self._fallbacks: Dict[Tuple[str, IsaMode], Fallback] = {}
        #: (base op, precision) -> quantized op name (ISSUE 7)
        self._precision_variants: Dict[Tuple[str, str], str] = {}
        #: base op -> TP twin op name (ISSUE 10): the sharded lowering
        #: that pays a collective, competing under auto when the ambient
        #: mesh carries a model axis
        self._collective_variants: Dict[str, str] = {}
        self.fallback_events: "collections.deque[FallbackEvent]" = \
            collections.deque(maxlen=self.EVENT_LOG_MAXLEN)

    # ---- registration (contract-checked) ----

    def register(self, op: str, mode, impl: Callable, *,
                 contract: Optional[KernelContract] = None,
                 cost: Optional[Callable[..., Mapping]] = None,
                 target: Optional[str] = None,
                 override: bool = False) -> Lowering:
        """Install a variant.  Raises :class:`ContractViolation` when the
        declared contract is out of budget, drifted (wrong op/mode), or
        illegal on its own target dialect."""
        mode = IsaMode(mode)
        if contract is None:
            if mode is not IsaMode.LIBRARY:
                raise ContractViolation(
                    f"{op} [{mode.value}]: non-library lowerings must "
                    f"declare a KernelContract")
            # the XLA-native op: no Pallas primitive budget to police
            contract = KernelContract(kernel=op, mode=IsaMode.LIBRARY,
                                      primitives=frozenset())
        if contract.kernel != op or contract.mode is not mode:
            raise ContractViolation(
                f"contract drift: registering {op} [{mode.value}] with a "
                f"contract for {contract.kernel} [{contract.mode.value}]")
        if contract.native_features and target is None:
            target = TARGET.name
        validate_contract(contract,
                          TARGET if target is None else get_dialect(target))
        # the dispatch layer injects plan_dialect= into every impl call
        # (kernels/ops.py::_dispatch) — enforce that signature contract
        # here, where the variant is declared, not at first dispatch
        try:
            params = inspect.signature(impl).parameters
        except (TypeError, ValueError):   # C callables etc.: trust them
            params = None
        if params is not None and "plan_dialect" not in params and not any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()):
            raise ContractViolation(
                f"{op} [{mode.value}]: impl must accept a plan_dialect "
                f"keyword (the dispatch layer passes the policy's "
                f"dialect as a static staging-plan argument)")
        variants = self._variants.setdefault(op, {})
        if mode in variants and not override:
            raise ValueError(f"{op} [{mode.value}] already registered")
        low = Lowering(op=op, mode=mode, impl=impl, contract=contract,
                       cost=cost, target=target)
        variants[mode] = low
        return low

    def declare_fallback(self, op: str, missing, to, reason: str) -> None:
        """Declare that requesting ``missing`` for ``op`` legally lowers to
        ``to`` — the explicit replacement for silent mode rewrites."""
        missing, to = IsaMode(missing), IsaMode(to)
        self._fallbacks[(op, missing)] = Fallback(op, missing, to, reason)

    def register_precision_variant(self, base_op: str, precision: str,
                                   quant_op: str) -> None:
        """Declare that ``base_op`` under ``ExecutionPolicy(precision=)``
        dispatches to ``quant_op`` — the quantized twin registered as its
        own op (own contracts, own costs, own fallbacks).  Both ops must
        already be registered; the mapping is consulted once, at
        :meth:`select` entry, so every downstream decision (mode legality,
        auto ranking, declared fallbacks) runs against the quantized op's
        own rows."""
        if precision not in POLICY_PRECISIONS or precision in (None, "f32"):
            raise ValueError(f"not a quantized precision: {precision!r}")
        for name in (base_op, quant_op):
            if name not in self._variants:
                raise UnsupportedLowering(
                    f"precision variant maps unknown op {name!r}")
        self._precision_variants[(base_op, precision)] = quant_op

    def precision_variant(self, op: str, precision: Optional[str]
                          ) -> Optional[str]:
        """The quantized twin of ``op`` at ``precision``, if declared."""
        if precision in (None, "f32"):
            return None
        return self._precision_variants.get((op, precision))

    def register_collective_variant(self, base_op: str, tp_op: str) -> None:
        """Declare that ``tp_op`` is the tensor-parallel twin of
        ``base_op`` — a registered op whose structural cost carries a
        ``collective_hbm_equiv_bytes`` term.  Under ``mode="auto"`` with a
        model axis in the ambient mesh, the twin's legal variants join the
        base op's candidate set, so replicated-vs-TP is decided by the
        same cost ranking that decides everything else.  Both ops must
        already be registered; every mode of the twin must declare its
        collective term (validate_contracts.py gates this)."""
        for name in (base_op, tp_op):
            if name not in self._variants:
                raise UnsupportedLowering(
                    f"collective variant maps unknown op {name!r}")
        self._collective_variants[base_op] = tp_op

    def collective_variant(self, op: str) -> Optional[str]:
        """The TP twin of ``op``, if declared."""
        return self._collective_variants.get(op)

    def collective_variants(self) -> Dict[str, str]:
        """All declared base -> TP-twin pairs (drives the CI gate)."""
        return dict(self._collective_variants)

    def unregister(self, op: str, mode=None) -> None:
        if mode is None:
            self._variants.pop(op, None)
            for key in [k for k in self._fallbacks if k[0] == op]:
                del self._fallbacks[key]
            for key in [k for k, v in self._precision_variants.items()
                        if k[0] == op or v == op]:
                del self._precision_variants[key]
            for key in [k for k, v in self._collective_variants.items()
                        if k == op or v == op]:
                del self._collective_variants[key]
        else:
            self._variants.get(op, {}).pop(IsaMode(mode), None)

    # ---- introspection (drives benchmarks and CI validation) ----

    def ops(self) -> Tuple[str, ...]:
        return tuple(sorted(self._variants))

    def modes(self, op: str) -> Tuple[str, ...]:
        """Registered mode strings in canonical (portability) order."""
        modes = sorted(self._variants[op], key=_PORTABILITY.__getitem__)
        return tuple(m.value for m in modes)

    def variant(self, op: str, mode) -> Lowering:
        try:
            return self._variants[op][IsaMode(mode)]
        except KeyError:
            raise UnsupportedLowering(
                f"{op} has no registered {mode!r} lowering") from None

    def contracts(self, op: str) -> Tuple[KernelContract, ...]:
        modes = sorted(self._variants[op], key=_PORTABILITY.__getitem__)
        return tuple(self._variants[op][m].contract for m in modes)

    def structural_cost(self, op: str, mode, **shape) -> Mapping:
        return self.variant(op, mode).structural_cost(**shape)

    def fallback_for(self, op: str, mode) -> Optional[Fallback]:
        return self._fallbacks.get((op, IsaMode(mode)))

    # ---- legality ----

    def legal(self, op: str, mode, dialect: Dialect) -> bool:
        """Table V legality of a registered variant under ``dialect``."""
        low = self._variants[op].get(IsaMode(mode))
        if low is None:
            return False
        if low.target is not None and low.target != dialect.name:
            return False          # native lowerings are target-pinned
        try:
            validate_contract(low.contract, dialect)
            return True
        except ContractViolation:
            return False

    # ---- the dispatch point ----

    def select(self, op: str, policy: Optional[ExecutionPolicy] = None,
               shape: Optional[Mapping] = None) -> Lowering:
        """Resolve policy -> one legal Lowering (the single dispatch
        point every call site above repro/kernels routes through)."""
        policy = policy or current_policy() or DEFAULT_POLICY
        dialect = policy.resolved_dialect()
        # precision retarget (ISSUE 7): a policy carrying precision="int8"
        # dispatches to the quantized twin wherever one is declared — the
        # retargeted op then competes on its own contracts/costs/fallbacks
        quant_op = self.precision_variant(op, policy.precision)
        if quant_op is not None:
            op = quant_op
        try:
            variants = self._variants[op]
        except KeyError:
            raise UnsupportedLowering(f"unknown op {op!r}; registered: "
                                      f"{self.ops()}") from None
        if policy.mode != AUTO:
            mode = IsaMode(policy.mode)
            if mode in variants and self.legal(op, mode, dialect):
                return variants[mode]
            fb = self._fallbacks.get((op, mode))
            if fb is not None and fb.to in variants \
                    and self.legal(op, fb.to, dialect):
                self._record(op, mode.value, fb.to.value, fb.reason)
                return variants[fb.to]
            raise UnsupportedLowering(
                f"{op} [{mode.value}] is not a legal lowering for dialect "
                f"{dialect.name} and declares no fallback")
        # auto: cheapest legal non-library variant by structural cost,
        # ranked with the policy's dialect bound *explicitly* so the
        # dialect-aware cost terms (tuned-table lookups) read the dialect
        # being selected for — the same binding the dispatch layer then
        # threads into the kernel as its static plan_dialect argument
        candidates = [low for m, low in variants.items()
                      if m is not IsaMode.LIBRARY
                      and self.legal(op, m, dialect)]
        # mesh-sensitive ranking (ISSUE 10): with a model axis in the
        # ambient mesh, the declared TP twin's variants compete too — its
        # cost trades sharded weight streams against the collective term,
        # so the same shape picks TP-fused or replicated per mesh size
        tp_op = self._collective_variants.get(op)
        if tp_op is not None and tp_axis_size() > 1:
            candidates += [low for m, low
                           in self._variants.get(tp_op, {}).items()
                           if m is not IsaMode.LIBRARY
                           and self.legal(tp_op, m, dialect)]
        if candidates:
            shape = shape or {}
            return min(candidates,
                       key=lambda lo: cost_key(
                           lo.structural_cost(plan_dialect=dialect.name,
                                              **shape), lo.mode))
        library = variants.get(IsaMode.LIBRARY)
        if library is not None:
            self._record(op, AUTO, IsaMode.LIBRARY.value,
                         f"no portable lowering legal for {dialect.name}")
            return library
        raise UnsupportedLowering(
            f"{op}: no lowering legal for dialect {dialect.name} and no "
            f"library reference registered")

    def _record(self, op: str, requested: str, used: str,
                reason: str) -> None:
        event = FallbackEvent(op, requested, used, reason)
        self.fallback_events.append(event)
        warnings.warn(f"{op}: {requested} -> {used} ({reason})",
                      LoweringFallbackWarning, stacklevel=3)


#: the process-wide registry every kernel module installs its variants in
REGISTRY = LoweringRegistry()
