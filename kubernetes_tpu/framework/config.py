"""Scheduler configuration API.

KubeSchedulerConfiguration-shaped (pkg/scheduler/apis/config/types.go:37-198)
with versioned defaulting and validation: profiles, per-extension-point
plugin enable/disable, MultiPoint expansion
(apis/config/v1/default_plugins.go:30-52, runtime/framework.go:511), plugin
args, extenders, and the scheduler-wide knobs (parallelism,
percentageOfNodesToScore, backoff bounds).  Loadable from YAML.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

EXTENSION_POINTS = (
    "preEnqueue",
    "queueSort",
    "preFilter",
    "filter",
    "postFilter",
    "preScore",
    "score",
    "reserve",
    "permit",
    "preBind",
    "bind",
    "postBind",
)

# Default MultiPoint plugin list with score weights
# (apis/config/v1/default_plugins.go:30-52).
DEFAULT_MULTI_POINT: List[Tuple[str, int]] = [
    ("SchedulingGates", 0),
    ("PrioritySort", 0),
    ("NodeUnschedulable", 0),
    ("NodeName", 0),
    ("TaintToleration", 3),
    ("NodeAffinity", 2),
    ("NodePorts", 0),
    ("NodeResourcesFit", 1),
    ("VolumeRestrictions", 0),
    ("NodeVolumeLimits", 0),
    ("VolumeBinding", 0),
    ("VolumeZone", 0),
    ("PodTopologySpread", 2),
    ("InterPodAffinity", 2),
    ("DefaultPreemption", 0),
    ("NodeResourcesBalancedAllocation", 1),
    ("ImageLocality", 1),
    ("DefaultBinder", 0),
]

DEFAULT_SCHEDULER_NAME = "default-scheduler"

# Scheduler-relevant feature gates and their reference defaults
# (pkg/features/kube_features.go @ v1.31).
DEFAULT_FEATURE_GATES: List[Tuple[str, bool]] = [
    ("DynamicResourceAllocation", False),  # alpha
    ("SchedulerQueueingHints", True),
    ("VolumeCapacityPriority", False),  # alpha
]


@dataclass
class PluginRef:
    name: str
    weight: int = 0


@dataclass
class PluginSet:
    enabled: List[PluginRef] = field(default_factory=list)
    disabled: List[PluginRef] = field(default_factory=list)


@dataclass
class Plugins:
    """Per-extension-point sets + multiPoint (apis/config/types.go)."""

    multi_point: PluginSet = field(default_factory=PluginSet)
    pre_enqueue: PluginSet = field(default_factory=PluginSet)
    queue_sort: PluginSet = field(default_factory=PluginSet)
    pre_filter: PluginSet = field(default_factory=PluginSet)
    filter: PluginSet = field(default_factory=PluginSet)
    post_filter: PluginSet = field(default_factory=PluginSet)
    pre_score: PluginSet = field(default_factory=PluginSet)
    score: PluginSet = field(default_factory=PluginSet)
    reserve: PluginSet = field(default_factory=PluginSet)
    permit: PluginSet = field(default_factory=PluginSet)
    pre_bind: PluginSet = field(default_factory=PluginSet)
    bind: PluginSet = field(default_factory=PluginSet)
    post_bind: PluginSet = field(default_factory=PluginSet)

    def points(self):
        """(wire name, PluginSet) pairs for every extension point —
        derived from EXTENSION_POINTS/_SNAKE so a new point automatically
        participates in validation and dump_config."""
        return [("multiPoint", self.multi_point)] + [
            (ep, getattr(self, _SNAKE[ep])) for ep in EXTENSION_POINTS
        ]


@dataclass
class Extender:
    """HTTP extender config (apis/config/types.go Extender)."""

    url_prefix: str = ""
    filter_verb: str = ""
    prioritize_verb: str = ""
    bind_verb: str = ""
    preempt_verb: str = ""
    weight: int = 1
    enable_https: bool = False
    http_timeout_s: float = 30.0
    node_cache_capable: bool = False
    ignorable: bool = False
    managed_resources: List[str] = field(default_factory=list)


@dataclass
class Profile:
    scheduler_name: str = DEFAULT_SCHEDULER_NAME
    plugins: Plugins = field(default_factory=Plugins)
    plugin_config: Dict[str, dict] = field(default_factory=dict)
    percentage_of_nodes_to_score: Optional[int] = None


API_VERSION = "kubescheduler.config.k8s.io/v1"
SUPPORTED_API_VERSIONS = {
    API_VERSION,
    # v1beta3 reads convert to v1; for the modeled fields the shapes match
    "kubescheduler.config.k8s.io/v1beta3",
}


@dataclass
class SchedulerConfiguration:
    """KubeSchedulerConfiguration (types.go:37)."""

    parallelism: int = 16
    profiles: List[Profile] = field(default_factory=lambda: [Profile()])
    extenders: List[Extender] = field(default_factory=list)
    percentage_of_nodes_to_score: int = 0  # 0 = adaptive
    pod_initial_backoff_seconds: float = 1.0
    pod_max_backoff_seconds: float = 10.0
    batch_size: int = 512  # TPU extension: gang batch width
    # TPU extension: fast-path batches EXTEND up to this many pods when the
    # queue head stays signature-eligible — per-pod host cost is flat on
    # the sig_scan path, so bigger batches amortize the device round trip.
    fast_batch_max: int = 4096
    # TPU extension: fast batches SMALLER than this with an idle pipeline
    # commit on the host greedy (zero device round trips — the interactive
    # case); larger or pipelined batches take the device sig_scan kernel.
    fast_device_min: int = 1024
    # TPU extension: speculative wave dispatch for cross-pod-constraint
    # batches (spread / inter-pod terms): one parallel (P × N) speculation
    # pass + a term-factored conflict-resolution pass replaces the gang
    # scan's per-step peer contractions (ops/wave.py; bit-identical to the
    # serial order).  Off = every such batch takes the gang scan.
    wave_dispatch: bool = True
    # TPU extension: device-resident drain loop (ops/resident.py) for
    # signature-gated runs — usage state stays in HBM across runs via
    # donated buffers and whole runs place through a multi-round
    # speculation/admission fixed point, one d2h readback of packed
    # placements per run (bit-identical to the serial greedy; see
    # RESIDENT.md).  Off = large fast batches take the sig_scan kernel.
    resident_drain: bool = True
    # resident RUN width: fast batches extend up to this many pods when
    # the resident path is engaged (supersedes fast_batch_max there) —
    # bigger runs amortize the per-run host round trip.
    resident_run_max: int = 16384
    # speculation window per fixed-point round (clamped to the node
    # bucket): bounds the agreement prefix one round can admit.
    resident_window: int = 2048
    # finish unresolved run tails IN-KERNEL with the serial sig_scan
    # replay (fully device-resident; right when serial device steps are
    # cheap — accelerator backends).  Off = tails come back UNRESOLVED
    # and the host committer finishes them (right when host heaps beat
    # serial device steps — CPU backends).
    resident_serial_tail: bool = False
    # TPU extension: the workloads tier (ops/coscheduling.py) — gang/
    # coscheduling all-or-nothing admission + batched DRA claim allocation
    # + volume-topology kernel masks ride one fused dispatch with
    # device-side gang rollback (see WORKLOADS.md).  Off = gang pods
    # schedule individually (no quorum semantics) and DRA/volume pods fall
    # back to the serial one-pod host-plugin path — decision-identical for
    # DRA/volume (kill-switch identity, tests/test_coscheduling.py).
    gang_dispatch: bool = True
    # TPU extension: the counterfactual planner tier (ops/counterfactual.py,
    # kubernetes_tpu/planner/) — /debug/plan what-ifs ride one batched
    # [K, P, N] kernel dispatch.  Off = the same fork specs replay through
    # the serial forked-snapshot oracle (oracle/planner.py) — decision-
    # identical (kill-switch identity, tests/test_planner.py).
    planner_kernel: bool = True
    # TPU extension: the device telemetry ledger (observability/
    # kernels.py) — per-kernel dispatch/compile/d2h accounting over every
    # registered jit root, served at /debug/kernels and /metrics, with
    # the execute-time regression sentinel wired into the SLO tier's
    # black-box dump.  Off = the root wrappers reduce to one global read
    # + branch per dispatch and nothing records (decision-identical
    # either way: the ledger only observes).
    kernel_ledger: bool = True
    # TPU extension: mesh-partitioned dispatch (parallel/mesh.py,
    # MULTICHIP.md) — the unified admission engine's inputs are placed on
    # the ('pods', 'nodes') device mesh, so every hot kernel (wave /
    # workloads / resident / counterfactual) runs SPMD-partitioned: pod
    # batches shard the pods axis (zero-collective speculation), node-major
    # snapshot tensors shard the nodes axis (per-term carries reduce
    # across shards; GSPMD inserts the psum/all-gather at the conflict
    # compare and final argmax).  None = AUTO: on whenever the backend
    # exposes more than one device.  Decisions are bit-identical in every
    # mode (multichip_vs_singlechip paritycheck, tests/test_multichip.py).
    mesh_dispatch: Optional[bool] = None
    # pods axis of the mesh (devices / pods_axis = nodes axis).  None =
    # make_mesh default: all devices on the pods axis — the layout with
    # zero collectives in the hot path (right for small clusters / big
    # batches); 1 puts every device on the nodes axis (right for huge
    # clusters).
    mesh_pods_axis: Optional[int] = None
    # Bit-compat knobs (SURVEY §7 "decision-identical tie-breaking"):
    # full-width evaluation is the TPU-native default; these opt into the
    # reference's sampling + randomized-tie semantics.
    #   reference_sampling_compat: apply numFeasibleNodesToFind's adaptive
    #     formula even when percentageOfNodesToScore is 0 (the reference
    #     always samples; our default is full width).
    #   tie_break_seed: seeded uniform tie-break among max-score nodes (the
    #     deterministic analogue of selectHost's reservoir sampling); None
    #     keeps first-max-in-node-order.
    reference_sampling_compat: bool = False
    tie_break_seed: Optional[int] = None
    # component-base/featuregate tier (pkg/features/kube_features.go) —
    # only the scheduler-relevant gates exist
    feature_gates: Dict[str, bool] = field(
        default_factory=lambda: dict(DEFAULT_FEATURE_GATES)
    )

    def validate(self) -> None:
        """The apis/config/validation table, scaled to this build's
        surface (validation.go ValidateKubeSchedulerConfiguration)."""
        names = [p.scheduler_name for p in self.profiles]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate profile names: {names}")
        if not self.profiles:
            raise ValueError("at least one profile required")
        if self.parallelism <= 0:
            raise ValueError("parallelism must be positive")
        if self.pod_initial_backoff_seconds <= 0:
            raise ValueError("podInitialBackoffSeconds must be positive")
        if self.pod_max_backoff_seconds < self.pod_initial_backoff_seconds:
            raise ValueError("podMaxBackoffSeconds < podInitialBackoffSeconds")
        if not 0 <= self.percentage_of_nodes_to_score <= 100:
            raise ValueError("percentageOfNodesToScore must be in [0, 100]")
        if self.batch_size <= 0:
            raise ValueError("batchSize must be positive")
        if self.mesh_pods_axis is not None and self.mesh_pods_axis <= 0:
            raise ValueError("meshPodsAxis must be positive")
        for key in (
            "fastBatchMax", "fastDeviceMin", "residentRunMax", "residentWindow"
        ):
            if getattr(self, _SCALAR_KEYS[key]) <= 0:
                raise ValueError(f"{key} must be positive")
        for p in self.profiles:
            if not p.scheduler_name:
                raise ValueError("profile schedulerName must be non-empty")
            if p.percentage_of_nodes_to_score is not None and not (
                0 <= p.percentage_of_nodes_to_score <= 100
            ):
                raise ValueError(
                    "profile percentageOfNodesToScore must be in [0, 100]"
                )
            for point_name, plugin_set in p.plugins.points():
                enabled = [r.name for r in plugin_set.enabled]
                if len(set(enabled)) != len(enabled):
                    raise ValueError(
                        f"duplicate plugin in {point_name} enabled list: "
                        f"{enabled}"
                    )
        binders = [e for e in self.extenders if e.bind_verb]
        if len(binders) > 1:
            raise ValueError("only one extender may implement bind")
        for e in self.extenders:
            if not e.url_prefix:
                raise ValueError("extender urlPrefix is required")
            if not 0 < e.weight:
                raise ValueError("extender weight must be positive")
            if e.ignorable and e.bind_verb:
                raise ValueError("a binding extender cannot be ignorable")


# ---------------------------------------------------------------------------
# Defaulting + MultiPoint expansion (runtime/framework.go:511 expandMultiPoint)
# ---------------------------------------------------------------------------

# Which extension points each in-tree plugin actually implements.
PLUGIN_POINTS: Dict[str, Tuple[str, ...]] = {
    "SchedulingGates": ("preEnqueue",),
    "PrioritySort": ("queueSort",),
    "NodeUnschedulable": ("filter",),
    "NodeName": ("filter",),
    "TaintToleration": ("filter", "preScore", "score"),
    "NodeAffinity": ("preFilter", "filter", "preScore", "score"),
    "NodePorts": ("preFilter", "filter"),
    "NodeResourcesFit": ("preFilter", "filter", "preScore", "score"),
    "VolumeRestrictions": ("preFilter", "filter"),
    "NodeVolumeLimits": ("preFilter", "filter"),
    "VolumeBinding": ("preFilter", "filter", "reserve", "preBind", "score"),
    "VolumeZone": ("preFilter", "filter"),
    "PodTopologySpread": ("preFilter", "filter", "preScore", "score"),
    "InterPodAffinity": ("preFilter", "filter", "preScore", "score"),
    "DefaultPreemption": ("postFilter",),
    "DynamicResources": ("preEnqueue", "preFilter", "filter", "reserve", "preBind"),
    "NodeResourcesBalancedAllocation": ("preScore", "score"),
    "ImageLocality": ("score",),
    "DefaultBinder": ("bind",),
}

_SNAKE = {
    "preEnqueue": "pre_enqueue",
    "queueSort": "queue_sort",
    "preFilter": "pre_filter",
    "filter": "filter",
    "postFilter": "post_filter",
    "preScore": "pre_score",
    "score": "score",
    "reserve": "reserve",
    "permit": "permit",
    "preBind": "pre_bind",
    "bind": "bind",
    "postBind": "post_bind",
}


def default_plugins(feature_gates: Optional[Dict[str, bool]] = None) -> Plugins:
    """Default plugin set, adjusted for feature gates
    (apis/config/v1/default_plugins.go getDefaultPlugins/applyFeatureGates)."""
    p = Plugins()
    refs = [PluginRef(n, w) for n, w in DEFAULT_MULTI_POINT]
    if (feature_gates or {}).get("DynamicResourceAllocation"):
        binder = next(i for i, r in enumerate(refs) if r.name == "DefaultBinder")
        refs.insert(binder, PluginRef("DynamicResources", 0))
    p.multi_point.enabled = refs
    return p


def _merge_plugin_set(default: PluginSet, custom: PluginSet) -> PluginSet:
    """mergePluginSet (apis/config/v1/default_plugins.go:107): defaults
    minus custom-disabled, with same-named custom entries replacing the
    default IN PLACE (order preserved), then remaining custom appended."""
    disabled_names = {d.name for d in custom.disabled}
    custom_by_name = {e.name: (i, e) for i, e in enumerate(custom.enabled)}
    replaced = set()
    enabled: List[PluginRef] = []
    if "*" not in disabled_names:
        for d in default.enabled:
            if d.name in disabled_names:
                continue
            hit = custom_by_name.get(d.name)
            if hit is not None:
                i, e = hit
                enabled.append(e)
                replaced.add(i)
            else:
                enabled.append(d)
    enabled.extend(
        e for i, e in enumerate(custom.enabled) if i not in replaced
    )
    return PluginSet(enabled=enabled, disabled=list(custom.disabled))


def expand_profile(
    profile: Profile, feature_gates: Optional[Dict[str, bool]] = None
) -> Dict[str, List[PluginRef]]:
    """MultiPoint expansion + per-point enable/disable merge.

    Returns extensionPoint → ordered [PluginRef] with effective weights.
    Rules (runtime/framework.go:511-600): per-point Enabled appends after
    multipoint expansion; per-point Disabled removes multipoint entries for
    that point only; '*' disables all; per-point weight overrides multipoint
    weight.
    """
    plugins = profile.plugins
    # Defaults are merged before expansion (apis/config/v1
    # default_plugins.go:107 mergePluginSet): user-enabled plugins override
    # same-named defaults in place or append; disabled names (or '*') drop
    # defaults.
    mp = _merge_plugin_set(
        default_plugins(feature_gates).multi_point, plugins.multi_point
    )
    mp_disabled = {d.name for d in mp.disabled}
    mp_all_disabled = "*" in mp_disabled

    out: Dict[str, List[PluginRef]] = {ep: [] for ep in EXTENSION_POINTS}
    for ep in EXTENSION_POINTS:
        point_set: PluginSet = getattr(plugins, _SNAKE[ep])
        point_disabled = {d.name for d in point_set.disabled}
        point_all_disabled = "*" in point_disabled
        seen = set()

        if not mp_all_disabled:
            for ref in mp.enabled:
                if ref.name in mp_disabled or ref.name in seen:
                    continue
                if ep not in PLUGIN_POINTS.get(ref.name, ()):
                    continue
                if point_all_disabled or ref.name in point_disabled:
                    continue
                # per-point weight overrides multipoint weight
                override = next(
                    (e for e in point_set.enabled if e.name == ref.name), None
                )
                weight = override.weight if override and override.weight else ref.weight
                out[ep].append(PluginRef(ref.name, weight or _default_weight(ref.name, ep)))
                seen.add(ref.name)

        for ref in point_set.enabled:
            if ref.name in seen:
                continue
            out[ep].append(PluginRef(ref.name, ref.weight or _default_weight(ref.name, ep)))
            seen.add(ref.name)
    return out


def _default_weight(name: str, ep: str) -> int:
    if ep != "score":
        return 0
    return dict(DEFAULT_MULTI_POINT).get(name, 1) or 1


# ---------------------------------------------------------------------------
# YAML loading (cmd/kube-scheduler/app/options/configfile.go analogue)
# ---------------------------------------------------------------------------


def _plugin_set_from(d: Optional[dict]) -> PluginSet:
    d = d or {}
    return PluginSet(
        enabled=[
            PluginRef(e["name"], e.get("weight", 0)) for e in d.get("enabled", [])
        ],
        disabled=[
            PluginRef(e["name"], e.get("weight", 0)) for e in d.get("disabled", [])
        ],
    )


def _plugins_from(d: Optional[dict]) -> Plugins:
    d = d or {}
    p = Plugins()
    p.multi_point = _plugin_set_from(d.get("multiPoint"))
    for ep in EXTENSION_POINTS:
        setattr(p, _SNAKE[ep], _plugin_set_from(d.get(ep)))
    return p


# v1 wire key → SchedulerConfiguration field, for every scalar option: the
# loader and the dumper both walk it, so a key is spelled once
_SCALAR_KEYS: Dict[str, str] = {
    "parallelism": "parallelism",
    "percentageOfNodesToScore": "percentage_of_nodes_to_score",
    "podInitialBackoffSeconds": "pod_initial_backoff_seconds",
    "podMaxBackoffSeconds": "pod_max_backoff_seconds",
    "batchSize": "batch_size",
    "fastBatchMax": "fast_batch_max",
    "fastDeviceMin": "fast_device_min",
    "waveDispatch": "wave_dispatch",
    "residentDrain": "resident_drain",
    "residentRunMax": "resident_run_max",
    "residentWindow": "resident_window",
    "residentSerialTail": "resident_serial_tail",
    "gangDispatch": "gang_dispatch",
    "plannerKernel": "planner_kernel",
    "kernelLedger": "kernel_ledger",
    "meshDispatch": "mesh_dispatch",
    "meshPodsAxis": "mesh_pods_axis",
    "referenceSamplingCompat": "reference_sampling_compat",
    "tieBreakSeed": "tie_break_seed",
}


def load_config(source) -> SchedulerConfiguration:
    """Load from a YAML string / path / dict."""
    from kubernetes_tpu.util.yamlsource import load_yaml_source

    d = load_yaml_source(source)
    kind = d.get("kind", "KubeSchedulerConfiguration")
    if kind != "KubeSchedulerConfiguration":
        raise ValueError(f"unexpected kind {kind!r}")
    # Versioned-kind tier (apis/config/scheme: v1 is served; v1beta3
    # converts on read — its wire shape for the fields this build models
    # is identical, so conversion is the identity here; unknown versions
    # fail loudly instead of half-applying).
    api_version = d.get("apiVersion", API_VERSION)
    if api_version not in SUPPORTED_API_VERSIONS:
        raise ValueError(
            f"unsupported apiVersion {api_version!r} "
            f"(supported: {sorted(SUPPORTED_API_VERSIONS)})"
        )

    profiles = []
    for pd in d.get("profiles", [{}]):
        plugin_config = {
            e["name"]: e.get("args", {}) for e in pd.get("pluginConfig", [])
        }
        profiles.append(
            Profile(
                scheduler_name=pd.get("schedulerName", DEFAULT_SCHEDULER_NAME),
                plugins=_plugins_from(pd.get("plugins")),
                plugin_config=plugin_config,
                percentage_of_nodes_to_score=pd.get("percentageOfNodesToScore"),
            )
        )
    extenders = [
        Extender(
            url_prefix=e.get("urlPrefix", ""),
            filter_verb=e.get("filterVerb", ""),
            prioritize_verb=e.get("prioritizeVerb", ""),
            bind_verb=e.get("bindVerb", ""),
            preempt_verb=e.get("preemptVerb", ""),
            weight=e.get("weight", 1),
            enable_https=e.get("enableHTTPS", False),
            http_timeout_s=e.get("httpTimeout", 30.0),
            node_cache_capable=e.get("nodeCacheCapable", False),
            ignorable=e.get("ignorable", False),
            managed_resources=[
                r.get("name") for r in e.get("managedResources", [])
            ],
        )
        for e in d.get("extenders", [])
    ]
    # a key the document leaves out keeps the dataclass's default: the
    # field is the one place a default lives
    cfg = SchedulerConfiguration(
        profiles=profiles or [Profile()],
        extenders=extenders,
        **{f: d[key] for key, f in _SCALAR_KEYS.items() if key in d},
    )
    if "featureGates" in d:
        cfg.feature_gates = dict(DEFAULT_FEATURE_GATES)
        cfg.feature_gates.update(d["featureGates"])
    cfg.validate()
    return cfg


def dump_config(cfg: SchedulerConfiguration) -> dict:
    """Serialize back to the v1 wire shape — load_config(dump_config(c))
    round-trips (the write half of the conversion tier)."""

    def plugin_set(ps: PluginSet):
        out = {}
        if ps.enabled:
            out["enabled"] = [
                {"name": r.name, **({"weight": r.weight} if r.weight else {})}
                for r in ps.enabled
            ]
        if ps.disabled:
            out["disabled"] = [{"name": r.name} for r in ps.disabled]
        return out

    profiles = []
    for p in cfg.profiles:
        pd = {"schedulerName": p.scheduler_name}
        plugins = {
            wire: plugin_set(ps)
            for wire, ps in p.plugins.points()
            if ps.enabled or ps.disabled
        }
        if plugins:
            pd["plugins"] = plugins
        if p.plugin_config:
            pd["pluginConfig"] = [
                {"name": name, "args": args}
                for name, args in p.plugin_config.items()
            ]
        if p.percentage_of_nodes_to_score is not None:
            pd["percentageOfNodesToScore"] = p.percentage_of_nodes_to_score
        profiles.append(pd)
    out = {
        "apiVersion": API_VERSION,
        "kind": "KubeSchedulerConfiguration",
        **{key: getattr(cfg, f) for key, f in _SCALAR_KEYS.items()},
        "featureGates": dict(cfg.feature_gates),
        "profiles": profiles,
    }
    if cfg.extenders:
        out["extenders"] = [
            {
                "urlPrefix": e.url_prefix,
                "filterVerb": e.filter_verb,
                "prioritizeVerb": e.prioritize_verb,
                "bindVerb": e.bind_verb,
                "preemptVerb": e.preempt_verb,
                "weight": e.weight,
                "enableHTTPS": e.enable_https,
                "httpTimeout": e.http_timeout_s,
                "nodeCacheCapable": e.node_cache_capable,
                "ignorable": e.ignorable,
                "managedResources": [
                    {"name": n} for n in e.managed_resources
                ],
            }
            for e in cfg.extenders
        ]
    return out
