"""utils/knobs: the typed GS_* registry every env read goes through.

Pins the contract the migration relied on: live per-call reads,
unset/empty = default, clamps instead of surprises at the declared
bounds, typed KnobError (naming knob + value + kind) on malformed
text, and the README table rendered from the registry so docs can't
drift (gslint R3 enforces the same diff tree-wide)."""

import os

import pytest

from gelly_streaming_tpu.utils import knobs

pytestmark = pytest.mark.lint

ALL = ("GS_PIPELINE_WORKERS GS_PIPELINE_INFLIGHT GS_STREAM_PREFETCH "
       "GS_STAGE_TIMEOUT_S GS_STAGE_RETRIES GS_STAGE_BACKOFF_S "
       "GS_TIER_RETRY_WINDOWS GS_TIER_DEMOTE GS_MESH_DEMOTE "
       "GS_MESH_WIRE_CHECK GS_AUTOTUNE GS_AUTOTUNE_ROUND "
       "GS_AUTOTUNE_EXPLORE GS_TUNE_CACHE "
       "GS_RESIDENT GS_RESIDENT_SPB GS_RESIDENT_SLOTS "
       "GS_PALLAS_WINDOW GS_PALLAS_TILE GS_PALLAS_CK "
       "GS_EGRESS GS_EGRESS_CAP "
       "GS_TELEMETRY GS_TRACE_DIR GS_TRACE_RING "
       "GS_TRACE_DURABLE GS_METRICS GS_METRICS_PORT "
       "GS_METRICS_SERIES GS_METRICS_COMPILE_BASE "
       "GS_HEALTH_STALE_S "
       "GS_TENANT_MAX GS_TENANT_QUEUE_WINDOWS GS_TENANT_ADMISSION "
       "GS_TENANT_TPD GS_COHORT_RESIDENT GS_COHORT_PALLAS "
       "GS_WAL GS_WAL_RETAIN GS_WAL_FSYNC_S GS_WAL_SEGMENT_BYTES "
       "GS_SERVE_PORT GS_SERVE_DRAIN_S GS_SERVE_IDLE_S "
       "GS_LATENCY GS_LAT_MARKS GS_LAT_PENDING "
       "GS_SLO_P99_S GS_SLO_BUDGET GS_SLO_WINDOW_S GS_SLO_BURN "
       "GS_SANITIZE GS_DLQ_DIR GS_DLQ_RETAIN "
       "GS_QUARANTINE_WINDOWS GS_MAX_BATCH_EDGES "
       "GS_PUMP GS_SLIDE GS_OOO_BOUND GS_SUB_QUEUE "
       "GS_GNN_F GS_GNN_ACT GS_GNN_PALLAS "
       "GS_PROVENANCE GS_PROVENANCE_DIR GS_PROVENANCE_RETAIN "
       "GS_COSTMODEL").split()

_GETTERS = {"int": knobs.get_int, "float": knobs.get_float,
            "bool": knobs.get_bool, "str": knobs.get_str,
            "path": knobs.get_path}


@pytest.fixture(autouse=True)
def clean_env(monkeypatch):
    for name in ALL:
        monkeypatch.delenv(name, raising=False)


def test_registry_contents():
    """Exactly the package's knob set — a new knob must be registered
    here (and lands in the README table by rendering)."""
    assert sorted(knobs.REGISTRY) == sorted(ALL)


def test_registry_round_trip_defaults():
    """Every registered knob reads through its kind's getter with the
    env unset, returning the declared default."""
    for name, knob in knobs.REGISTRY.items():
        value = _GETTERS[knob.kind](name)
        if knob.default is None:
            assert value is None, name
        elif knob.kind == "bool":
            assert value is bool(knob.default), name
        else:
            assert value == knob.default, name


def test_unset_and_empty_mean_default(monkeypatch):
    assert knobs.get_int("GS_TRACE_RING") == 4096
    monkeypatch.setenv("GS_TRACE_RING", "")
    assert knobs.get_int("GS_TRACE_RING") == 4096
    monkeypatch.setenv("GS_TELEMETRY", "")
    assert knobs.get_bool("GS_TELEMETRY") is False


def test_int_parse_and_clamp(monkeypatch):
    monkeypatch.setenv("GS_STAGE_RETRIES", "7")
    assert knobs.get_int("GS_STAGE_RETRIES") == 7
    monkeypatch.setenv("GS_STAGE_RETRIES", "-3")   # lo=0
    assert knobs.get_int("GS_STAGE_RETRIES") == 0
    monkeypatch.setenv("GS_TRACE_RING", "4")       # lo=16
    assert knobs.get_int("GS_TRACE_RING") == 16
    monkeypatch.setenv("GS_AUTOTUNE_EXPLORE", "1")  # lo=2
    assert knobs.get_int("GS_AUTOTUNE_EXPLORE") == 2


def test_float_parse_and_clamp(monkeypatch):
    monkeypatch.setenv("GS_STAGE_TIMEOUT_S", "2.5")
    assert knobs.get_float("GS_STAGE_TIMEOUT_S") == 2.5
    monkeypatch.setenv("GS_STAGE_TIMEOUT_S", "-1")
    assert knobs.get_float("GS_STAGE_TIMEOUT_S") == 0.0
    assert knobs.get_float("GS_STAGE_BACKOFF_S") == 0.05


def test_bool_parse(monkeypatch):
    for text, want in (("1", True), ("true", True), ("YES", True),
                       ("on", True), ("0", False), ("false", False),
                       ("No", False), ("off", False)):
        monkeypatch.setenv("GS_TIER_DEMOTE", text)
        assert knobs.get_bool("GS_TIER_DEMOTE") is want, text


def test_str_choices(monkeypatch):
    assert knobs.get_str("GS_EGRESS") == ""
    monkeypatch.setenv("GS_EGRESS", "delta")
    assert knobs.get_str("GS_EGRESS") == "delta"
    monkeypatch.setenv("GS_EGRESS", "sideways")
    with pytest.raises(knobs.KnobError):
        knobs.get_str("GS_EGRESS")


def test_egress_accepts_documented_auto(monkeypatch):
    # the README table renders GS_EGRESS's default as `full`; setting
    # the documented default explicitly must behave like unset, and
    # the retired `auto` value is refused rather than ignored
    from gelly_streaming_tpu.ops import delta_egress
    monkeypatch.delenv("GS_EGRESS", raising=False)
    assert delta_egress.resolve_egress() == "full"
    monkeypatch.setenv("GS_EGRESS", "full")
    assert delta_egress.resolve_egress() == "full"
    monkeypatch.setenv("GS_EGRESS", "auto")
    with pytest.raises(knobs.KnobError):
        knobs.get_str("GS_EGRESS")


def _pin_resolvers():
    from gelly_streaming_tpu.ops import pallas_window, resident_engine
    return {"GS_RESIDENT": resident_engine.resolve_resident,
            "GS_COHORT_RESIDENT": resident_engine.resolve_resident_cohort,
            "GS_PALLAS_WINDOW": pallas_window.resolve_pallas_window,
            "GS_COHORT_PALLAS": pallas_window.resolve_cohort_pallas,
            "GS_GNN_PALLAS": pallas_window.resolve_gnn_pallas}


@pytest.mark.parametrize("name", ["GS_RESIDENT", "GS_COHORT_RESIDENT",
                                  "GS_PALLAS_WINDOW", "GS_COHORT_PALLAS",
                                  "GS_GNN_PALLAS"])
def test_tier_pin_is_on_or_off_only(monkeypatch, name):
    """A tier pin selects its tier only at `on`; `off` is the same as
    unset, and the retired `auto` is refused rather than ignored."""
    resolve = _pin_resolvers()[name]
    assert resolve() is False
    monkeypatch.setenv(name, "off")
    assert resolve() is False
    monkeypatch.setenv(name, "on")
    assert resolve() is True
    monkeypatch.setenv(name, "auto")
    with pytest.raises(knobs.KnobError):
        resolve()


def test_path_kind(monkeypatch):
    assert knobs.get_path("GS_TRACE_DIR") is None
    monkeypatch.setenv("GS_TRACE_DIR", "/tmp/ledger")
    assert knobs.get_path("GS_TRACE_DIR") == "/tmp/ledger"
    monkeypatch.setenv("GS_TUNE_CACHE", "0")  # conventional "disabled"
    assert knobs.get_path("GS_TUNE_CACHE") == "0"


@pytest.mark.parametrize("name,getter,bad", [
    ("GS_STAGE_RETRIES", knobs.get_int, "3O"),
    ("GS_STAGE_TIMEOUT_S", knobs.get_float, "fast"),
    ("GS_TELEMETRY", knobs.get_bool, "maybe"),
    ("GS_EGRESS_CAP", knobs.get_int, "1e3"),
])
def test_malformed_raises_typed(monkeypatch, name, getter, bad):
    """A mistyped knob fails FAST and NAMED instead of silently
    running at the default the operator didn't ask for."""
    monkeypatch.setenv(name, bad)
    with pytest.raises(knobs.KnobError) as exc:
        getter(name)
    assert name in str(exc.value)
    assert bad in str(exc.value)
    assert exc.value.knob is knobs.REGISTRY[name]
    assert isinstance(exc.value, ValueError)  # old callers still catch


def test_reads_are_live(monkeypatch):
    """No caching: tools/chaos_run.py and the fault tests flip knobs
    mid-process and the next read must see it."""
    monkeypatch.setenv("GS_STAGE_RETRIES", "1")
    assert knobs.get_int("GS_STAGE_RETRIES") == 1
    monkeypatch.setenv("GS_STAGE_RETRIES", "2")
    assert knobs.get_int("GS_STAGE_RETRIES") == 2


def test_kind_mismatch_is_programming_error():
    with pytest.raises(AssertionError):
        knobs.get_int("GS_TELEMETRY")       # declared bool
    with pytest.raises(AssertionError):
        knobs.get_bool("GS_NO_SUCH_KNOB")   # unregistered


def test_migrated_call_sites_resolve_through_registry(monkeypatch):
    """The five migrated modules' helpers read the registry (a spot
    check per module; gslint R3 proves the tree-wide absence of raw
    reads)."""
    from gelly_streaming_tpu.ops import autotune, delta_egress
    from gelly_streaming_tpu.ops import ingress_pipeline
    from gelly_streaming_tpu.utils import resilience, telemetry

    monkeypatch.setenv("GS_STAGE_TIMEOUT_S", "1.5")
    assert resilience.stage_timeout_s() == 1.5
    monkeypatch.setenv("GS_TELEMETRY", "1")
    assert telemetry.enabled() is True
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    assert autotune.enabled() is False
    monkeypatch.setenv("GS_EGRESS_CAP", "64")
    assert delta_egress.egress_cap(1024, 4096) == 64
    monkeypatch.setenv("GS_PIPELINE_INFLIGHT", "5")
    assert ingress_pipeline.inflight_limit() == 5
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    assert autotune.cache_path("cpu") == ""


def test_render_table_matches_readme():
    """The committed README contains the registry-rendered knob table
    verbatim — the doc-drift fixture gslint R3 also diffs."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    table = knobs.render_table()
    assert table in readme
    assert len(table.splitlines()) == len(ALL) + 2  # header + rule
