"""Config API: defaulting, MultiPoint expansion, YAML loading, validation."""

import pytest

from kubernetes_tpu.framework import config as cfg
from kubernetes_tpu.framework.registry import default_registry
from kubernetes_tpu.framework.runtime import Framework


def test_default_profile_expansion():
    prof = cfg.Profile()
    pts = cfg.expand_profile(prof)
    score_names = {r.name: r.weight for r in pts["score"]}
    # default weights (apis/config/v1/default_plugins.go:30-52)
    assert score_names["TaintToleration"] == 3
    assert score_names["NodeAffinity"] == 2
    assert score_names["PodTopologySpread"] == 2
    assert score_names["InterPodAffinity"] == 2
    assert score_names["NodeResourcesFit"] == 1
    assert score_names["NodeResourcesBalancedAllocation"] == 1
    assert score_names["ImageLocality"] == 1
    filter_names = [r.name for r in pts["filter"]]
    assert "NodeResourcesFit" in filter_names
    assert "PodTopologySpread" in filter_names
    assert [r.name for r in pts["queueSort"]] == ["PrioritySort"]
    assert [r.name for r in pts["bind"]] == ["DefaultBinder"]
    assert [r.name for r in pts["preEnqueue"]] == ["SchedulingGates"]


def test_multipoint_disable_and_weight_override():
    prof = cfg.Profile()
    prof.plugins.multi_point.disabled = [cfg.PluginRef("ImageLocality")]
    prof.plugins.score.enabled = [cfg.PluginRef("NodeAffinity", weight=7)]
    pts = cfg.expand_profile(prof)
    score = {r.name: r.weight for r in pts["score"]}
    assert "ImageLocality" not in score
    assert score["NodeAffinity"] == 7


def test_point_disable_star():
    prof = cfg.Profile()
    prof.plugins.score.disabled = [cfg.PluginRef("*")]
    pts = cfg.expand_profile(prof)
    assert pts["score"] == []
    assert [r.name for r in pts["bind"]] == ["DefaultBinder"]


def test_yaml_load_and_framework():
    y = """
apiVersion: kubescheduler.config.k8s.io/v1
kind: KubeSchedulerConfiguration
parallelism: 8
podInitialBackoffSeconds: 2
podMaxBackoffSeconds: 20
profiles:
  - schedulerName: tpu-scheduler
    plugins:
      multiPoint:
        disabled:
          - name: ImageLocality
      score:
        enabled:
          - name: NodeResourcesFit
            weight: 5
    pluginConfig:
      - name: NodeResourcesFit
        args:
          scoringStrategy:
            type: MostAllocated
"""
    c = cfg.load_config(y)
    assert c.parallelism == 8
    assert c.pod_initial_backoff_seconds == 2
    fwk = Framework(c.profiles[0], default_registry())
    assert fwk.profile_name == "tpu-scheduler"
    assert fwk.score_weights["NodeResourcesFit"] == 5
    assert "ImageLocality" not in fwk.score_weights
    assert "NodeResourcesFit" in fwk.device_enabled()
    inst = fwk._instances["NodeResourcesFit"]
    assert inst.args["scoringStrategy"]["type"] == "MostAllocated"


def test_validation_rejects_bad_config():
    with pytest.raises(ValueError):
        cfg.load_config({"kind": "Wrong"})
    c = cfg.SchedulerConfiguration(pod_initial_backoff_seconds=-1)
    with pytest.raises(ValueError):
        c.validate()
    c = cfg.SchedulerConfiguration()
    c.profiles = [cfg.Profile(), cfg.Profile()]
    with pytest.raises(ValueError):
        c.validate()


def test_events_to_register_surface():
    fwk = Framework(cfg.Profile(), default_registry())
    evs = fwk.events_to_register()
    assert "NodeResourcesFit" in evs
    assert "SchedulingGates" in evs


# ---------------------------------------------------------------------------
# versioned-kind tier: v1 round-trip + validation rejections (Missing #6)
# ---------------------------------------------------------------------------


def test_v1_round_trip():
    from kubernetes_tpu.framework.config import dump_config, load_config

    src = {
        "apiVersion": "kubescheduler.config.k8s.io/v1",
        "kind": "KubeSchedulerConfiguration",
        "parallelism": 8,
        "percentageOfNodesToScore": 50,
        "podInitialBackoffSeconds": 0.5,
        "podMaxBackoffSeconds": 5.0,
        "batchSize": 128,
        "referenceSamplingCompat": True,
        "tieBreakSeed": 1234,
        "featureGates": {"DynamicResourceAllocation": True},
        "profiles": [
            {
                "schedulerName": "default-scheduler",
                "plugins": {
                    "score": {
                        "enabled": [{"name": "NodeResourcesFit", "weight": 5}],
                        "disabled": [{"name": "ImageLocality"}],
                    }
                },
                "pluginConfig": [
                    {
                        "name": "NodeResourcesFit",
                        "args": {
                            "scoringStrategy": {"type": "MostAllocated"}
                        },
                    }
                ],
            },
            {"schedulerName": "batch-scheduler"},
        ],
        "extenders": [
            {
                "urlPrefix": "http://127.0.0.1:9999/ext",
                "filterVerb": "filter",
                "weight": 2,
            }
        ],
    }
    cfg = load_config(dict(src))
    wire = dump_config(cfg)
    cfg2 = load_config(wire)
    # round-trip fixed point: dumping again is byte-identical
    assert dump_config(cfg2) == wire
    assert cfg2.parallelism == 8
    assert [p.scheduler_name for p in cfg2.profiles] == [
        "default-scheduler",
        "batch-scheduler",
    ]
    assert cfg2.extenders[0].url_prefix == "http://127.0.0.1:9999/ext"
    assert (
        cfg2.profiles[0]
        .plugin_config["NodeResourcesFit"]["scoringStrategy"]["type"]
        == "MostAllocated"
    )
    # the bit-compat knobs round-trip — losing them would silently change
    # placement decisions on reload
    assert cfg2.reference_sampling_compat is True
    assert cfg2.tie_break_seed == 1234
    assert cfg2.feature_gates["DynamicResourceAllocation"] is True


def test_v1beta3_reads_convert():
    from kubernetes_tpu.framework.config import load_config

    cfg = load_config(
        {
            "apiVersion": "kubescheduler.config.k8s.io/v1beta3",
            "kind": "KubeSchedulerConfiguration",
            "parallelism": 4,
        }
    )
    assert cfg.parallelism == 4


@pytest.mark.parametrize(
    "mutation,msg",
    [
        ({"apiVersion": "kubescheduler.config.k8s.io/v9"}, "unsupported apiVersion"),
        ({"kind": "SchedulerPolicy"}, "unexpected kind"),
        ({"parallelism": 0}, "parallelism"),
        ({"percentageOfNodesToScore": 101}, "percentageOfNodesToScore"),
        ({"podInitialBackoffSeconds": 0}, "podInitialBackoffSeconds"),
        ({"batchSize": -1}, "batchSize"),
        (
            {
                "profiles": [
                    {"schedulerName": "a"},
                    {"schedulerName": "a"},
                ]
            },
            "duplicate profile names",
        ),
        ({"profiles": [{"schedulerName": ""}]}, "schedulerName"),
        (
            {
                "profiles": [
                    {
                        "plugins": {
                            "score": {
                                "enabled": [
                                    {"name": "NodeResourcesFit"},
                                    {"name": "NodeResourcesFit"},
                                ]
                            }
                        }
                    }
                ]
            },
            "duplicate plugin",
        ),
        (
            {"extenders": [{"filterVerb": "filter"}]},
            "urlPrefix",
        ),
        (
            {"extenders": [{"urlPrefix": "http://x", "weight": 0}]},
            "weight",
        ),
        (
            {
                "extenders": [
                    {"urlPrefix": "http://x", "bindVerb": "bind"},
                    {"urlPrefix": "http://y", "bindVerb": "bind"},
                ]
            },
            "one extender",
        ),
        (
            {
                "extenders": [
                    {
                        "urlPrefix": "http://x",
                        "bindVerb": "bind",
                        "ignorable": True,
                    }
                ]
            },
            "ignorable",
        ),
    ],
)
def test_v1_validation_rejections(mutation, msg):
    from kubernetes_tpu.framework.config import load_config

    with pytest.raises(ValueError, match=msg):
        load_config({**V1, **mutation})


# ---------------------------------------------------------------------------
# TPU-extension options: each camelCase key loads, round-trips and is
# range-checked where a range exists; each default has one home
# ---------------------------------------------------------------------------

V1 = {
    "apiVersion": "kubescheduler.config.k8s.io/v1",
    "kind": "KubeSchedulerConfiguration",
}


@pytest.mark.parametrize(
    "key,value,refused",
    [
        ("fastBatchMax", 64, -1),
        ("fastDeviceMin", 8, -1),
        ("waveDispatch", False, None),
        ("residentDrain", False, None),
        ("residentRunMax", 256, 0),
        ("residentWindow", 32, -2048),
        ("residentSerialTail", True, None),
        ("gangDispatch", False, None),
        ("plannerKernel", False, None),
        ("kernelLedger", False, None),
        ("meshDispatch", False, None),
        ("meshPodsAxis", 2, 0),
    ],
)
def test_tpu_option_loads_round_trips_and_is_range_checked(key, value, refused):
    import dataclasses

    from kubernetes_tpu.framework.config import dump_config, load_config

    field = cfg._SCALAR_KEYS[key]
    default = cfg.SchedulerConfiguration()
    assert getattr(default, field) != value
    # a document without the key keeps the dataclass's default
    assert load_config(dict(V1)) == default
    loaded = load_config({**V1, key: value})
    # the key set that one field and no other
    assert loaded == dataclasses.replace(default, **{field: value})
    doc = dump_config(loaded)
    assert doc[key] == value
    assert load_config(doc) == loaded
    if refused is not None:
        with pytest.raises(ValueError, match=key):
            load_config({**V1, key: refused})


def test_each_default_and_each_wire_key_has_one_home():
    """The dataclass holds the defaults: the scheduler reads options as
    attributes (no ``getattr(self.config, name, default)`` with a second
    default), and the loader and dumper walk one table that covers every
    scalar field."""
    import dataclasses
    import inspect

    from kubernetes_tpu import scheduler

    assert "getattr(self.config" not in inspect.getsource(scheduler)
    scalar = {f.name for f in dataclasses.fields(cfg.SchedulerConfiguration)} - {
        "profiles",
        "extenders",
        "feature_gates",
    }
    assert set(cfg._SCALAR_KEYS.values()) == scalar
    assert "resident_epoch_guard" not in scalar
