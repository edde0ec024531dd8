"""`ServeConfig`: one declarative record for the whole serving pipeline.

`TangramScheduler` had accreted ~10 orthogonal keyword arguments
(batching knobs, executor mode, pool size, placement, estimator, clock),
and `launch/serve.py` mirrored each as an ad-hoc CLI flag.  This module
consolidates them into a single frozen dataclass grouped by subsystem,
designed so a config can be **logged into benchmark JSON and rebuilt**
from it:

* every field is a plain value or a *named reference* — classifiers,
  placements, clocks, executors, sources and models are referred to by
  their registry names (``make_classify`` / ``make_placement`` /
  ``make_clock`` / ``make_executor`` / ``make_source`` /
  ``make_model`` resolve them), never by callables or meshes;
* ``to_dict()`` / ``from_dict()`` round-trip through ``json`` exactly
  (nested ``AIMDConfig`` included), and ``dataclasses.replace`` works
  for one-field sweeps.

The old keyword arguments still work through a deprecation shim on
``TangramScheduler`` that warns once and forwards here.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

from repro.core.adaptive import AIMDConfig
from repro.core.partitioning import Patch
from repro.core.registry import lookup

#: classifier registry: named references for the `classify` field.  None
#: (the paper's single shared queue) is spelled as the name ``None`` /
#: JSON null.  Register project classifiers here so configs stay
#: serializable.
_CLASSIFIERS: dict = {}


def register_classify(name: str, fn: Callable[[Patch], object]) -> None:
    _CLASSIFIERS[name] = fn


def make_classify(name: Optional[str]
                  ) -> Optional[Callable[[Patch], object]]:
    """Classifier-name -> callable (``"slo"`` | ``None``), the named-
    reference resolution for ``ServeConfig.classify``."""
    if name is None:
        return None
    if not _CLASSIFIERS:
        from repro.core.engine import slo_class
        _CLASSIFIERS["slo"] = slo_class
    return lookup("classifier", _CLASSIFIERS, name)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything the serving pipeline needs beyond data + models.

    Grouped by subsystem; each group's fields resolve through the
    matching factory.  All fields are JSON-safe by construction.
    """

    # --- batching (invoker pool) ---------------------------------------
    max_canvases: int = 8            # canvas budget per invocation (Eq. 5)
    incremental: bool = True         # live PackState vs literal restitch
    classify: Optional[str] = None   # None: shared queue; "slo": per-class
    adaptive: Optional[AIMDConfig] = None  # AIMD controller on the pool

    # --- execution ------------------------------------------------------
    executor: str = "sim"            # sim | device | async_device
    use_pallas: bool = False         # no effect: the unfused device path
                                     # stitches and unstitches on the host
    fuse: bool = False               # fused stitch->embed / decode->gather
                                     # device hot path (fused_embed.py)
    quantize: bool = False           # serve int8-resident weights: models
                                     # resolve to their _int8 registry
                                     # variants, the ad-hoc detector builds
                                     # quantized
    max_inflight: int = 4            # async in-flight bound (device memory)
    clock: str = "virtual"           # virtual | wall
    wall_speed: float = 1.0          # engine seconds per wall second
    check_invariants: bool = False

    # --- worker pool ----------------------------------------------------
    n_workers: int = 1
    placement: Optional[str] = None  # least | round | affinity | model
                                     # (None: least)

    # --- fleet sharding (core.fleet) ------------------------------------
    shards: Optional[int] = None     # None: one engine; N: ShardedEngine
                                     # with N camera-group shards (then
                                     # n_workers is the TOTAL worker
                                     # budget split across shards)
    planner: Optional[str] = None    # cost | equal — shard layout planner
                                     # (None: "cost" when shards is set)
    parallel: bool = False           # run each shard's engine loop on its
                                     # own thread (ParallelShardedEngine);
                                     # False keeps the sequential path
                                     # bit-identical to PR 9

    # --- models (registry names; see repro.core.models) -----------------
    model: Optional[str] = None      # default model for every class (None:
                                     # the implicit single-model pipeline)
    model_map: Optional[Dict[str, str]] = None
                                     # SLO class (as str) -> model name;
                                     # classes not in the map fall back to
                                     # ``model``

    # --- latency estimator ----------------------------------------------
    online_latency: bool = False     # OnlineLatencyTable feedback loop

    # --- ingestion (source layer) ---------------------------------------
    source: str = "trace"            # trace | synthetic | file
    ingestion_window: Optional[int] = None  # backlog bound, in patches

    def __post_init__(self):
        if self.n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {self.n_workers}")
        if self.max_inflight < 1:
            raise ValueError(
                f"max_inflight must be >= 1, got {self.max_inflight}")
        if self.wall_speed <= 0:
            raise ValueError(
                f"wall_speed must be positive, got {self.wall_speed}")
        if self.ingestion_window is not None and self.ingestion_window < 1:
            raise ValueError(f"ingestion_window must be >= 1, got "
                             f"{self.ingestion_window}")
        if self.shards is not None and self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.planner is not None and self.shards is None:
            raise ValueError("planner requires shards to be set")
        if self.parallel and self.shards is None:
            raise ValueError("parallel requires shards to be set")

    def replace(self, **changes) -> "ServeConfig":
        return dataclasses.replace(self, **changes)

    # ------------------------------------------------------ model routing ----

    @property
    def multi_model(self) -> bool:
        """True when model identity is threaded explicitly (a default
        model and/or a class->model map is configured)."""
        return self.model is not None or bool(self.model_map)

    def resolve_model(self, key: object) -> Optional[str]:
        """SLO class key -> registry model name.  Class keys are matched
        against ``model_map`` by their ``str()`` (JSON object keys are
        strings); misses fall back to the default ``model``."""
        if self.model_map:
            name = self.model_map.get(str(key))
            if name is not None:
                return name
        return self.model

    def model_names(self) -> list:
        """Every registry model this config references (sorted)."""
        names = set(self.model_map.values()) if self.model_map else set()
        if self.model is not None:
            names.add(self.model)
        return sorted(names)

    # ------------------------------------------------------ serialization ----

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)   # AIMDConfig -> nested plain dict
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ServeConfig":
        d = dict(d)
        adaptive = d.get("adaptive")
        if isinstance(adaptive, dict):
            d["adaptive"] = AIMDConfig(**adaptive)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown ServeConfig fields {sorted(unknown)}")
        return cls(**d)
