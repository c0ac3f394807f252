"""Heal pacer proofs (minio_tpu/background/healpace, ISSUE 17): config
from env, the env kill switch, token-pool serialization, the deadline
grant that makes MRF-drain starvation impossible by construction, the
background-class latency filter, sliding-window p99 semantics, the
MRFHealer pressure-stretched drain interval, and the metrics mirror."""

import threading
import time

import pytest

from minio_tpu.background import healpace
from minio_tpu.background.healpace import HealPacer, PaceConfig


@pytest.fixture(autouse=True)
def _fresh_pacer():
    """Every test starts and ends without a process pacer installed."""
    healpace.reset()
    yield
    healpace.reset()


# ---------------------------------------------------------------------------
# config plane


def test_config_defaults_and_env_overrides(monkeypatch):
    cfg = PaceConfig.from_env()
    assert cfg.enabled and cfg.tokens == 2 and cfg.queue_high == 2
    assert cfg.disk_p99_ms == 75.0 and cfg.max_wait_s == 2.0
    monkeypatch.setenv("MTPU_HEAL_PACE_TOKENS", "5")
    monkeypatch.setenv("MTPU_HEAL_PACE_QUEUE_HIGH", "9")
    monkeypatch.setenv("MTPU_HEAL_PACE_DISK_P99_MS", "150")
    monkeypatch.setenv("MTPU_HEAL_PACE_MAX_WAIT_MS", "500")
    cfg = PaceConfig.from_env()
    assert (cfg.tokens, cfg.queue_high, cfg.disk_p99_ms,
            cfg.max_wait_s) == (5, 9, 150.0, 0.5)
    # Garbage values fall back, and the pool floor is 1 token.
    monkeypatch.setenv("MTPU_HEAL_PACE_TOKENS", "0")
    monkeypatch.setenv("MTPU_HEAL_PACE_MAX_WAIT_MS", "lots")
    cfg = PaceConfig.from_env()
    assert cfg.tokens == 1 and cfg.max_wait_s == 2.0


def test_env_kill_switch_makes_every_surface_inert(monkeypatch):
    """MTPU_HEAL_PACE=off (the 1-core deployment posture): slots grant
    immediately without counting, pressure always reads False, and the
    latency feed drops samples at the door."""
    monkeypatch.setenv("MTPU_HEAL_PACE", "off")
    p = healpace.reconfigure()
    assert not p.cfg.enabled
    with p.heal_slot():
        with p.heal_slot():  # no token accounting at all
            pass
    assert p.snapshot()["grants_total"] == 0
    assert not p.pressured()
    healpace.note_disk_op(5.0)
    assert p.snapshot()["disk_p99_ms"] == 0.0


# ---------------------------------------------------------------------------
# the slot: tokens, yields, deadline grants


def test_token_pool_caps_concurrent_heals():
    p = HealPacer(PaceConfig(enabled=True, tokens=2, max_wait_s=10.0),
                  pressure_probe=lambda: False)
    peak = [0]
    mu = threading.Lock()

    def heal():
        with p.heal_slot():
            with mu:
                peak[0] = max(peak[0], p.snapshot()["inflight"])
            time.sleep(0.03)

    threads = [threading.Thread(target=heal) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert peak[0] <= 2
    assert p.snapshot()["grants_total"] == 8
    assert p.snapshot()["inflight"] == 0


def test_permanent_pressure_never_deadlocks_the_drain():
    """The ISSUE 17 starvation proof: a probe that ALWAYS reports
    foreground pressure still grants every heal within max_wait_s (as a
    counted deadline grant) — a sequence of heals completes in bounded
    time instead of wedging the MRF drain."""
    p = HealPacer(
        PaceConfig(enabled=True, tokens=1, max_wait_s=0.1, yield_s=0.01),
        pressure_probe=lambda: True,
    )
    t0 = time.monotonic()
    for _ in range(20):
        with p.heal_slot():
            pass
    elapsed = time.monotonic() - t0
    snap = p.snapshot()
    assert snap["grants_total"] == 20
    assert snap["deadline_grants_total"] == 20
    assert snap["yields_total"] > 0
    # 20 heals x 0.1s deadline each, generous slop for CI weather.
    assert elapsed < 20 * 0.1 * 3, f"drain took {elapsed:.1f}s"


def test_clean_path_grants_without_yielding():
    p = HealPacer(PaceConfig(enabled=True, tokens=2, max_wait_s=2.0),
                  pressure_probe=lambda: False)
    with p.heal_slot():
        pass
    snap = p.snapshot()
    assert snap["grants_total"] == 1
    assert snap["deadline_grants_total"] == 0
    assert snap["yields_total"] == 0


def test_probe_exception_does_not_leak_or_wedge():
    """A blown pressure probe must not leave the token pool corrupted:
    the slot either grants or propagates, and a following heal still
    completes."""
    calls = [0]

    def probe():
        calls[0] += 1
        raise RuntimeError("probe blew up")

    p = HealPacer(PaceConfig(enabled=True, tokens=1, max_wait_s=0.2),
                  pressure_probe=probe)
    with pytest.raises(RuntimeError):
        with p.heal_slot():
            pass
    # Pool not corrupted: a healthy-probe pacer sharing nothing fails
    # nothing, and this pacer's inflight count is still 0.
    assert p.snapshot()["inflight"] == 0


# ---------------------------------------------------------------------------
# the pressure inputs


def test_p99_needs_min_samples_then_tracks_tail():
    p = HealPacer(PaceConfig(enabled=True), pressure_probe=lambda: False)
    for _ in range(10):
        p.note_foreground_disk(0.001)
    assert p.disk_p99_s() == 0.0, "p99 from a handful of samples is noise"
    for _ in range(90):
        p.note_foreground_disk(0.001)
    p.note_foreground_disk(0.9)  # one tail outlier in ~100 samples
    assert p.disk_p99_s() >= 0.001
    for _ in range(50):
        p.note_foreground_disk(0.9)  # now the tail IS slow
    assert p.disk_p99_s() == pytest.approx(0.9)


def test_default_pressure_trips_on_queue_depth_and_p99():
    p = HealPacer(PaceConfig(enabled=True, queue_high=2, disk_p99_ms=50.0))
    # Neither input present: governors idle, no latency samples.
    assert not p.pressured()
    # Span-measured foreground p99 over the threshold trips it.
    for _ in range(40):
        p.note_foreground_disk(0.2)
    assert p.pressured()


def test_pressure_fades_when_the_foreground_goes_quiet():
    """Slow foreground ops followed by silence must not throttle heals
    for ever: samples older than max_wait_s no longer count. (A ring of
    the last N samples, whatever their age, held a 64-client burst's
    tail over a whole MRF drain: every heal waited out its deadline.)"""
    import time

    p = HealPacer(PaceConfig(enabled=True, disk_p99_ms=50.0,
                             max_wait_s=0.05))
    for _ in range(40):
        p.note_foreground_disk(0.2)
    assert p.pressured()
    time.sleep(0.1)
    assert not p.pressured() and p.disk_p99_s() == 0.0


def test_note_disk_op_filters_background_ops():
    """Latencies measured under a background ioflow tag (heal/scan/
    replication) must NOT count as foreground pressure — the pacer
    would otherwise throttle heals in response to its own reads."""
    from minio_tpu.observability import ioflow

    p = healpace.reconfigure(PaceConfig(enabled=True))
    with ioflow.tag("heal"):
        for _ in range(40):
            healpace.note_disk_op(0.5)
    assert p.disk_p99_s() == 0.0
    with ioflow.tag("get", bucket="b"):
        for _ in range(40):
            healpace.note_disk_op(0.5)
    assert p.disk_p99_s() == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# process-global lifecycle + consumers


def test_installed_never_constructs_and_reset_clears():
    assert healpace.installed() is None
    healpace.note_disk_op(0.1)  # feed before install: cheap no-op
    assert healpace.installed() is None
    p = healpace.pacer()
    assert healpace.installed() is p
    healpace.reset()
    assert healpace.installed() is None


def test_mrf_healer_stretches_interval_under_pressure():
    from minio_tpu.background.heal import MRFHealer

    # No pacer installed: interval untouched.
    assert MRFHealer._pace_delay(0.5) == 0.5
    healpace.reconfigure(PaceConfig(enabled=True))
    healpace.installed()._probe = lambda: True
    assert 0.5 < MRFHealer._pace_delay(0.5) <= 2.5
    healpace.installed()._probe = lambda: False
    assert MRFHealer._pace_delay(0.5) == 0.5
    # Disabled pacer: untouched even under a lying probe.
    healpace.reconfigure(PaceConfig(enabled=False))
    healpace.installed()._probe = lambda: True
    assert MRFHealer._pace_delay(0.5) == 0.5


def test_metrics_collector_mirrors_pacer_state():
    from minio_tpu.observability.metrics import Metrics
    from minio_tpu.observability.metrics_v2 import MetricsCollector

    m = Metrics()
    col = MetricsCollector(m)
    col.collect()  # no pacer installed: no heal_pace series forced
    assert "heal_pace_grants_total 0" not in m.render_prometheus()

    p = healpace.reconfigure(PaceConfig(enabled=True, tokens=3))
    with p.heal_slot():
        pass
    col.collect()
    text = m.render_prometheus()
    assert "mtpu_heal_pace_tokens 3" in text
    assert "mtpu_heal_pace_grants_total 1" in text
    assert "mtpu_heal_pace_inflight 0" in text
