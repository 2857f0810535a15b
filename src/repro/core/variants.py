"""Function-variant registry (paper §III-A).

A *function variant* is a group of implementations with the same name,
arguments and result types, one per device kind.  Binding a logical
operation to a variant lets the runtime pick the implementation that
matches whatever compute lane the scheduler chose — CPU core, GPU,
TPU-interpret, ... — so heterogeneous devices are used concurrently and
in coordination.

The registry also carries per-variant *speedup estimates* (accelerator
vs one host core) which feed the PATS scheduler.  Estimates may be

* static (registered alongside the implementation),
* data-dependent (a callable of the chunk's ``meta``), or
* learned online from observed runtimes (exponential moving average),

mirroring the paper's observation that both per-operation and per-chunk
variability exist.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

__all__ = ["FunctionVariant", "VariantRegistry", "registry"]

SpeedupFn = Callable[[Mapping[str, Any]], float]


@dataclass
class FunctionVariant:
    """All registered implementations of one logical operation."""

    name: str
    impls: dict[str, Callable[..., Any]] = field(default_factory=dict)
    # accelerator-vs-host speedup estimate; key is accelerator kind
    speedup: dict[str, float] = field(default_factory=dict)
    speedup_fn: dict[str, SpeedupFn] = field(default_factory=dict)
    # fraction of exec time spent on host<->device transfers
    transfer_impact: float = 0.0
    # online estimator state: kind -> (ema_runtime, n_obs)
    _observed: dict[str, tuple[float, int]] = field(default_factory=dict)

    def implementation(self, device_kind: str) -> Callable[..., Any]:
        if device_kind in self.impls:
            return self.impls[device_kind]
        # Fall back to the host implementation: a variant is allowed to
        # exist only for some kinds (e.g. no accelerator port yet).
        if "cpu" in self.impls:
            return self.impls["cpu"]
        raise KeyError(
            f"variant {self.name!r} has no implementation for {device_kind!r}"
        )

    def supports(self, device_kind: str) -> bool:
        return device_kind in self.impls

    def estimate_speedup(
        self, device_kind: str, meta: Mapping[str, Any] | None = None
    ) -> float:
        """Estimated speedup of running on ``device_kind`` vs one host core."""
        if device_kind == "cpu":
            return 1.0
        # Online observations dominate once both kinds have been timed.
        obs = self._observed
        if "cpu" in obs and device_kind in obs and obs[device_kind][1] >= 2:
            return max(obs["cpu"][0] / max(obs[device_kind][0], 1e-12), 1e-6)
        if device_kind in self.speedup_fn and meta is not None:
            return self.speedup_fn[device_kind](meta)
        return self.speedup.get(device_kind, 1.0)

    def observe_runtime(self, device_kind: str, seconds: float) -> None:
        ema, n = self._observed.get(device_kind, (seconds, 0))
        alpha = 0.3
        self._observed[device_kind] = (alpha * seconds + (1 - alpha) * ema, n + 1)


class VariantRegistry:
    """Thread-safe name -> FunctionVariant map."""

    def __init__(self) -> None:
        self._variants: dict[str, FunctionVariant] = {}
        self._lock = threading.Lock()

    def register(
        self,
        name: str,
        device_kind: str,
        fn: Callable[..., Any],
        *,
        speedup: float | None = None,
        speedup_fn: SpeedupFn | None = None,
        transfer_impact: float | None = None,
    ) -> FunctionVariant:
        with self._lock:
            var = self._variants.setdefault(name, FunctionVariant(name))
            var.impls[device_kind] = fn
            if speedup is not None:
                var.speedup[device_kind] = speedup
            if speedup_fn is not None:
                var.speedup_fn[device_kind] = speedup_fn
            if transfer_impact is not None:
                var.transfer_impact = transfer_impact
            return var

    def cpu(self, name: str, **kw: Any) -> Callable[[Callable], Callable]:
        """Decorator: ``@registry.cpu("watershed")``."""
        return self._decorator(name, "cpu", **kw)

    def accel(self, name: str, kind: str = "gpu", **kw: Any):
        return self._decorator(name, kind, **kw)

    def _decorator(self, name: str, kind: str, **kw: Any):
        def deco(fn: Callable) -> Callable:
            self.register(name, kind, fn, **kw)
            return fn

        return deco

    def get(self, name: str) -> FunctionVariant:
        with self._lock:
            if name not in self._variants:
                raise KeyError(f"no function variant registered as {name!r}")
            return self._variants[name]

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._variants

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._variants)

    def clear(self) -> None:  # test hook
        with self._lock:
            self._variants.clear()


#: Process-global registry; applications may also instantiate their own.
registry = VariantRegistry()
