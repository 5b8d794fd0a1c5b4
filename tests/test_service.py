"""The resident campaign service: dispatch, telemetry export, northbound API.

The service-layer contracts, asserted here:

* **(a) zero-churn through the service == monolithic, bitwise** — a
  churn-free spec submitted over the HTTP API is lifted to its segmented
  streaming form (``as_streaming_spec``), executed with per-segment
  checkpoints, and its completed history is bitwise-equal to
  ``ArchesSession.run()`` on every leaf; the API reports segment
  progress, spec_hash provenance (submitted *and* lifted run form) and
  the checkpoint lineage throughout.
* **(b) drain / kill -> restart resumes bitwise** — a drain requested at
  a chosen segment boundary (in-process, deterministic) and a real
  SIGTERM delivered to a ``python -m repro.service`` child mid-campaign
  both leave an ``interrupted`` campaign whose restarted service resumes
  it from the latest checkpoint to a history bitwise-equal to the
  uninterrupted ``run_streaming()`` (the PR 8 ``resume_from=`` contract
  carried through the service path).
* **(c) telemetry is lossless or exactly counted** — the ring's ``push``
  is O(1) under its lock and never waits on a consumer; ``drain(cursor)``
  reports *exactly* the overwritten-sample count under wrap-around and
  under concurrent producers (sequence arithmetic, not sampling); the
  JSONL exporter receives every sample the pump drained, in order.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core.runtime import BatchedRunHistory
from repro.core.session import (
    ArchesSession,
    CampaignSpec,
    as_streaming_spec,
    spec_hash,
)
from repro.core.streaming import ChurnSchedule
from repro.core.telemetry import segment_telemetry
from repro.service import (
    CampaignService,
    CampaignState,
    ExportPump,
    JsonlExporter,
    ServiceSaturatedError,
    TelemetryRing,
    UnknownCampaignError,
)

N_PRB = 6
N_UES = 4
N_SLOTS = 12
SEG = 4


def _modes_grid(n_slots: int, n_ues: int) -> tuple:
    return tuple(
        tuple((s + u) % 2 for u in range(n_ues)) for s in range(n_slots)
    )


def _base_spec(**kw) -> CampaignSpec:
    args = dict(
        path="batched", scenario="churn_cell", n_ues=N_UES,
        n_slots=N_SLOTS, n_prb=N_PRB, seed=3,
        modes=_modes_grid(N_SLOTS, N_UES),
    )
    args.update(kw)
    return CampaignSpec(**args)


def assert_history_equal(a, b):
    np.testing.assert_array_equal(a.modes, b.modes, err_msg="modes")
    assert set(a.kpms) == set(b.kpms)
    for k in a.kpms:
        np.testing.assert_array_equal(a.kpms[k], b.kpms[k], err_msg=k)
    assert set(a.outputs) == set(b.outputs)
    for k in a.outputs:
        np.testing.assert_array_equal(a.outputs[k], b.outputs[k], err_msg=k)


# -- telemetry ring: wrap-around + concurrency, drops exactly counted ---------


def test_ring_validation_and_basic_drain():
    with pytest.raises(ValueError, match="capacity"):
        TelemetryRing(0)
    ring = TelemetryRing(8)
    assert ring.head == 0
    seqs = [ring.push({"i": i}) for i in range(5)]
    assert seqs == [0, 1, 2, 3, 4]
    samples, cursor, dropped = ring.drain(0)
    assert [s["i"] for s in samples] == [0, 1, 2, 3, 4]
    assert (cursor, dropped) == (5, 0)
    # nothing new: empty drain, cursor stable
    samples, cursor, dropped = ring.drain(cursor)
    assert (samples, cursor, dropped) == ([], 5, 0)


def test_ring_wraparound_drop_count_is_exact():
    ring = TelemetryRing(4)
    for i in range(10):
        ring.push(i)
    # cursor 0: samples 0..5 were overwritten -> exactly 6 dropped
    samples, cursor, dropped = ring.drain(0)
    assert samples == [6, 7, 8, 9]
    assert (cursor, dropped) == (10, 6)
    # an up-to-date cursor then sees no loss
    ring.push(10)
    samples, cursor, dropped = ring.drain(cursor)
    assert (samples, cursor, dropped) == ([10], 11, 0)
    # a cursor mid-way through the overwritten span counts only its own loss
    samples, _, dropped = ring.drain(5)
    assert samples == [7, 8, 9, 10]
    assert dropped == 2  # samples 5, 6


def test_ring_snapshot_is_cursor_free():
    ring = TelemetryRing(4)
    for i in range(6):
        ring.push(i)
    assert ring.snapshot() == [2, 3, 4, 5]
    assert ring.snapshot(2) == [4, 5]
    assert ring.snapshot(99) == [2, 3, 4, 5]
    # snapshot does not advance any drain cursor
    _, _, dropped = ring.drain(0)
    assert dropped == 2


def test_ring_concurrent_producers_and_consumer_account_every_sample():
    """N producers + 1 draining consumer: delivered + dropped == pushed,
    and the delivered sequence numbers are strictly increasing (no
    duplicates, no uncounted gaps)."""
    ring = TelemetryRing(16)
    n_producers, per_producer = 4, 500
    total = n_producers * per_producer

    def produce(pid):
        for i in range(per_producer):
            ring.push({"pid": pid, "i": i, "seq": None})

    seen: list = []
    dropped_total = 0
    stop = threading.Event()

    def consume():
        nonlocal dropped_total
        cursor = 0
        while not stop.is_set() or cursor < ring.head:
            samples, new_cursor, dropped = ring.drain(cursor)
            seen.extend(range(cursor + dropped, new_cursor))
            dropped_total += dropped
            cursor = new_cursor

    threads = [
        threading.Thread(target=produce, args=(p,))
        for p in range(n_producers)
    ]
    consumer = threading.Thread(target=consume)
    consumer.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    consumer.join()

    assert ring.head == total
    assert len(seen) + dropped_total == total
    assert seen == sorted(set(seen)), "duplicate or reordered delivery"


def test_ring_push_never_blocks_on_a_stalled_consumer():
    """A consumer sitting on a stale cursor costs producers nothing: push
    latency is flat while the ring wraps thousands of times."""
    ring = TelemetryRing(4)
    t0 = time.perf_counter()
    for i in range(20_000):
        ring.push(i)
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, f"push path blocked: {elapsed:.2f}s for 20k pushes"
    _, _, dropped = ring.drain(0)
    assert dropped == 20_000 - 4


# -- exporters + pump ---------------------------------------------------------


def test_jsonl_exporter_receives_every_drained_sample(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    ring = TelemetryRing(64)
    pump = ExportPump(ring, [JsonlExporter(path)])
    for i in range(20):
        ring.push({"seg_idx": i})
    assert pump.pump_once() == 20
    ring.push({"seg_idx": 20})
    assert pump.pump_once() == 1
    for ex in pump.exporters:
        ex.close()
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    assert [r["seg_idx"] for r in rows] == list(range(21))
    assert pump.counters() == {
        "exported": 21, "dropped": 0, "export_errors": 0,
    }


def test_pump_counts_ring_drops_exactly():
    ring = TelemetryRing(4)
    sink: list = []

    class ListExporter:
        def export(self, samples):
            sink.extend(samples)

        def close(self):
            pass

    pump = ExportPump(ring, [ListExporter()])
    for i in range(10):
        ring.push(i)
    pump.pump_once()
    assert sink == [6, 7, 8, 9]
    assert pump.dropped == 6
    assert pump.exported == 4


def test_pump_swallows_and_counts_exporter_errors():
    ring = TelemetryRing(8)
    good: list = []

    class Broken:
        def export(self, samples):
            raise RuntimeError("sink down")

    class Good:
        def export(self, samples):
            good.extend(samples)

    pump = ExportPump(ring, [Broken(), Good()])
    ring.push({"x": 1})
    pump.pump_once()  # must not raise
    assert good == [{"x": 1}]
    assert pump.export_errors == 1
    assert pump.exported == 1


# -- segment telemetry reduction ----------------------------------------------


def test_segment_telemetry_masks_residency_and_fallbacks():
    modes = np.array([[0, 1], [0, 0], [-1, 0]], np.int32)
    attached = np.array([[1, 1], [1, 1], [0, 1]], bool)
    tput = np.array([[10.0, 20.0], [30.0, 40.0], [0.0, 50.0]], np.float32)
    flops = np.array([[5.0, 0.0], [5.0, 5.0], [0.0, 5.0]], np.float32)
    overflow = np.array([[0, 0], [1, 0], [0, 0]], np.int32)
    hist = BatchedRunHistory(
        modes=modes,
        kpms={"phy_throughput": tput},
        outputs={"executed_flops": flops, "gated_overflow": overflow},
        attached=attached,
        cell_of_ue=np.array([0, 1], np.int32),
    )
    out = segment_telemetry(hist, 0, 3)
    assert out["resident_slot_ues"] == 5
    # served-by-AI: mode==0 & resident & not overflowed ->
    # (0,0), (1,1), (2,1): 3 of 5 residents
    assert out["ai_share"] == pytest.approx(3 / 5)
    assert out["throughput_bps"] == pytest.approx(
        (10.0 + 20.0 + 30.0 + 40.0 + 50.0) / 5
    )
    assert out["executed_flops"] == pytest.approx(20.0)
    assert out["gated_overflow_slot_ues"] == 1
    assert out["per_cell_throughput_bps"] == [
        pytest.approx((10.0 + 30.0) / 2),
        pytest.approx((20.0 + 40.0 + 50.0) / 3),
    ]
    # a sub-span reduces only its own slots
    sub = segment_telemetry(hist, 2, 3)
    assert sub["resident_slot_ues"] == 1
    assert sub["throughput_bps"] == pytest.approx(50.0)
    with pytest.raises(ValueError, match="outside"):
        segment_telemetry(hist, 2, 5)


# -- spec lifting -------------------------------------------------------------


def test_as_streaming_spec_lifts_zero_churn():
    spec = _base_spec()
    lifted = as_streaming_spec(spec, max_segment_slots=SEG)
    assert lifted.churn == ChurnSchedule(
        n_ue_ids=N_UES, segment_slots=SEG, initial=tuple(range(N_UES))
    )
    assert spec_hash(lifted) != spec_hash(spec)
    # idempotent on already-streaming specs
    assert as_streaming_spec(lifted) is lifted
    # segment length: largest divisor of n_slots <= the cap
    assert as_streaming_spec(spec, max_segment_slots=5).churn.segment_slots == 4
    assert as_streaming_spec(spec, max_segment_slots=7).churn.segment_slots == 6
    with pytest.raises(ValueError, match="streaming form"):
        as_streaming_spec(_base_spec(path="host", n_ues=1, modes=1))


# -- the service: queue-only control paths (no JAX execution) -----------------


def test_cancel_queued_and_unknown(tmp_path):
    svc = CampaignService(str(tmp_path / "s"))  # not started: stays queued
    cid = svc.submit(_base_spec())
    assert svc.status(cid)["state"] == CampaignState.QUEUED
    assert svc.cancel(cid) == CampaignState.CANCELLED
    assert svc.status(cid)["state"] == CampaignState.CANCELLED
    with pytest.raises(UnknownCampaignError):
        svc.status("c9999-deadbeef")
    with pytest.raises(UnknownCampaignError):
        svc.cancel("c9999-deadbeef")


def test_submit_saturation_is_explicit(tmp_path):
    svc = CampaignService(str(tmp_path / "s"), queue_size=1)
    cid = svc.submit(_base_spec())
    with pytest.raises(ServiceSaturatedError):
        svc.submit(_base_spec(seed=4))
    # the rejected campaign leaves no record or state-dir litter
    assert [c["campaign_id"] for c in svc.list_campaigns()] == [cid]
    assert os.listdir(svc.campaigns_dir) == [cid]


def test_recovery_exceeds_queue_size_without_blocking(tmp_path):
    """A restarted service re-enqueues *every* recoverable campaign even
    when there are more of them than its submission cap — a saturated
    service that crashed must recover, not deadlock in start()."""
    state = str(tmp_path / "s")
    svc = CampaignService(state, queue_size=4)  # not started: all stay queued
    cids = [svc.submit(_base_spec(seed=s)) for s in range(3, 7)]
    svc2 = CampaignService(state, queue_size=1)
    t = threading.Thread(target=svc2._recover, daemon=True)
    t.start()
    t.join(10.0)
    assert not t.is_alive(), "_recover() blocked on the dispatch queue"
    # all four recovered, in original submission order
    assert [svc2._queue.get_nowait() for _ in range(4)] == cids
    # the submission cap still applies to new submits while saturated
    with pytest.raises(ServiceSaturatedError):
        svc2.submit(_base_spec(seed=9))


def test_cancelled_and_torn_campaigns_not_recovered(tmp_path):
    state = str(tmp_path / "s")
    svc = CampaignService(state)
    cid_q = svc.submit(_base_spec())
    cid_c = svc.submit(_base_spec(seed=4))
    svc.cancel(cid_c)
    # torn submit: a directory with no status.json (crash mid-persist)
    os.makedirs(os.path.join(svc.campaigns_dir, "c9999-torn"))
    svc2 = CampaignService(state)
    svc2._recover()
    states = {c["campaign_id"]: c["state"] for c in svc2.list_campaigns()}
    assert states == {
        cid_q: CampaignState.QUEUED, cid_c: CampaignState.CANCELLED,
    }
    assert svc2._queue.qsize() == 1  # only the queued one re-enqueued
    # recovered ids continue the submission sequence (no id reuse)
    cid_new = svc2.submit(_base_spec(seed=5))
    assert int(cid_new[1:5]) == 3


# -- the service: execution contracts (shared compiled components) ------------


@pytest.fixture(scope="module")
def ref_session():
    return ArchesSession(_base_spec())


@pytest.fixture(scope="module")
def api_run(ref_session, tmp_path_factory):
    """One full service lifecycle over the northbound HTTP API.

    Submits the module's zero-churn campaign over HTTP, polls it to
    completion, then exercises every API route (including the error
    paths and the drain) against the live service.  Module-scoped so the
    engine compile happens once; the tests below assert on the captured
    outcome.
    """
    from repro.service.api import ServiceAPI

    state = str(tmp_path_factory.mktemp("svc-api"))
    jsonl = os.path.join(state, "telemetry.jsonl")
    svc = CampaignService(
        state,
        max_segment_slots=SEG,
        exporters=[JsonlExporter(jsonl)],
        ai_params=ref_session.ai_params,
    ).start()
    api = ServiceAPI(svc).start()
    base = api.url

    def get(path):
        try:
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    def post(path, payload=None):
        req = urllib.request.Request(
            base + path,
            data=json.dumps(payload).encode() if payload is not None else b"",
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    spec = ref_session.spec
    out: dict = {"spec": spec}
    code, body = post("/campaigns", spec.to_dict())
    assert code == 201
    cid = body["campaign_id"]
    out["cid"] = cid

    states_seen = []
    deadline = time.monotonic() + 180
    while True:
        code, st = get(f"/campaigns/{cid}")
        assert code == 200
        if not states_seen or states_seen[-1] != st["state"]:
            states_seen.append(st["state"])
        if st["state"] in CampaignState.TERMINAL:
            break
        assert time.monotonic() < deadline, f"stuck in {st['state']}"
        time.sleep(0.05)
    out["final_status"] = st
    out["states_seen"] = states_seen
    out["result"] = svc.result(cid)

    _, out["campaign_list"] = get("/campaigns")
    _, out["telemetry"] = get("/telemetry?n=2")
    _, out["telemetry_all"] = get("/telemetry")
    _, out["health"] = get("/health")
    out["bad_spec"] = post("/campaigns", {"path": "warp"})
    out["bad_telemetry_n"] = get("/telemetry?n=zap")
    out["unknown_get"] = get("/campaigns/c9999-deadbeef")
    out["unknown_cancel"] = post("/campaigns/c9999-deadbeef/cancel")
    out["no_route"] = get("/nope")

    out["drain_resp"] = post("/drain")
    assert svc.drain(timeout=30)
    out["submit_while_draining"] = post("/campaigns", spec.to_dict())
    api.stop()
    with open(jsonl) as f:
        out["jsonl_rows"] = [json.loads(line) for line in f]
    out["pump_counters"] = svc.pump.counters()
    out["state_dir"] = state
    return out


def test_service_zero_churn_bitwise_equals_monolithic(ref_session, api_run):
    assert api_run["final_status"]["state"] == CampaignState.COMPLETED
    assert_history_equal(api_run["result"], ref_session.run())


def test_api_reports_progress_provenance_and_lineage(api_run):
    st = api_run["final_status"]
    spec = api_run["spec"]
    assert api_run["states_seen"][-1] == CampaignState.COMPLETED
    assert set(api_run["states_seen"]) <= {
        CampaignState.QUEUED, CampaignState.RUNNING, CampaignState.COMPLETED,
    }
    assert st["n_segments"] == N_SLOTS // SEG
    assert st["segments_done"] == st["n_segments"]
    assert st["spec_hash"] == spec_hash(spec)
    assert st["run_spec_hash"] == spec_hash(
        as_streaming_spec(spec, max_segment_slots=SEG)
    )
    # checkpoint lineage: one complete checkpoint per segment, keep-3
    assert st["checkpoint_steps"] == [1, 2, 3]
    listed = api_run["campaign_list"]
    assert [c["campaign_id"] for c in listed] == [api_run["cid"]]
    assert listed[0]["spec_hash"] == spec_hash(spec)


def test_api_telemetry_and_health(api_run):
    n_segments = N_SLOTS // SEG
    rows = api_run["telemetry_all"]
    assert [r["seg_idx"] for r in rows] == list(range(n_segments))
    assert [r["seg_idx"] for r in api_run["telemetry"]] == [1, 2]
    for r in rows:
        assert r["campaign_id"] == api_run["cid"]
        assert r["resident_slot_ues"] == SEG * N_UES
        assert 0.0 <= r["ai_share"] <= 1.0
        assert r["throughput_bps"] > 0
        assert r["executed_flops"] > 0
    health = api_run["health"]
    assert health["status"] == "ok"
    assert health["campaign_states"] == {CampaignState.COMPLETED: 1}
    assert health["telemetry"]["samples_published"] == n_segments


def test_api_error_paths(api_run):
    assert api_run["bad_spec"][0] == 400
    assert api_run["bad_telemetry_n"][0] == 400
    assert api_run["unknown_get"][0] == 404
    assert api_run["unknown_cancel"][0] == 404
    assert api_run["no_route"][0] == 404
    assert api_run["drain_resp"][0] == 202
    assert api_run["submit_while_draining"][0] == 503


def test_jsonl_export_is_lossless(api_run):
    """Every published segment sample reached the JSONL sink, in order."""
    rows = api_run["jsonl_rows"]
    assert [r["seg_idx"] for r in rows] == list(range(N_SLOTS // SEG))
    assert api_run["pump_counters"]["dropped"] == 0
    assert api_run["pump_counters"]["export_errors"] == 0
    assert api_run["pump_counters"]["exported"] == len(rows)


_CHURN = ChurnSchedule(
    n_ue_ids=N_UES + 1, segment_slots=SEG,
    initial=tuple(range(N_UES - 1)),
    events=(
        (SEG, N_UES, "attach"),
        (SEG + 1, 0, "detach"),
        (2 * SEG, 0, "attach"),
    ),
)


def test_drain_then_restart_resumes_bitwise(ref_session, tmp_path):
    """Graceful drain at a chosen segment boundary -> interrupted campaign
    -> restarted service resumes it from the checkpoint -> the completed
    history is bitwise-equal to the uninterrupted streaming run."""
    spec = _base_spec(
        modes=_modes_grid(N_SLOTS, N_UES + 1), churn=_CHURN
    )
    ref = ArchesSession(spec, ai_params=ref_session.ai_params).run_streaming()

    state = str(tmp_path / "svc")

    def drain_after_first_segment(service, rec, ev):
        if ev.seg_idx == 0:
            service.request_drain()

    svc = CampaignService(
        state, max_segment_slots=SEG, ai_params=ref_session.ai_params,
        segment_callback=drain_after_first_segment,
    ).start()
    cid = svc.submit(spec)
    # the callback requests the drain from inside segment 0; wait for it
    # so the worker (not this thread) decides where to stop
    deadline = time.monotonic() + 120
    while not svc.draining:
        assert time.monotonic() < deadline, "segment callback never fired"
        time.sleep(0.02)
    assert svc.drain(timeout=120)
    st = svc.status(cid)
    assert st["state"] == CampaignState.INTERRUPTED
    assert st["segments_done"] == 1
    assert st["checkpoint_steps"] == [1]

    svc2 = CampaignService(
        state, max_segment_slots=SEG, ai_params=ref_session.ai_params,
    ).start()
    assert svc2.wait(cid, timeout=120) == CampaignState.COMPLETED
    st2 = svc2.status(cid)
    assert st2["segments_done"] == st2["n_segments"] == N_SLOTS // SEG
    # the lifted run form is the spec itself (it already declared churn)
    assert st2["run_spec_hash"] == st2["spec_hash"] == spec_hash(spec)
    assert_history_equal(svc2.result(cid), ref)
    np.testing.assert_array_equal(svc2.result(cid).attached, ref.attached)
    np.testing.assert_array_equal(svc2.result(cid).bank_slot, ref.bank_slot)
    # the resumed run's telemetry covers only the segments it executed
    assert [s["seg_idx"] for s in svc2.ring.snapshot()] == [1, 2]
    assert svc2.drain(timeout=30)


def test_cancel_running_stops_at_boundary_and_keeps_checkpoint(
    ref_session, tmp_path
):
    spec = _base_spec(seed=7)

    def cancel_after_first_segment(service, rec, ev):
        if ev.seg_idx == 0:
            rec.cancel_event.set()

    svc = CampaignService(
        str(tmp_path / "svc"), max_segment_slots=SEG,
        ai_params=ref_session.ai_params,
        segment_callback=cancel_after_first_segment,
    ).start()
    cid = svc.submit(spec)
    assert svc.wait(cid, timeout=120) == CampaignState.CANCELLED
    st = svc.status(cid)
    assert st["segments_done"] == 1
    assert st["checkpoint_steps"] == [1]  # retained for a later resubmit
    # cancelled campaigns are terminal: a restart does not resurrect them.
    # The first service stops before the restart: two live services on one
    # state dir race on its status files.
    assert svc.drain(timeout=30)
    svc2 = CampaignService(str(tmp_path / "svc"))
    svc2._recover()
    assert svc2.status(cid)["state"] == CampaignState.CANCELLED
    assert svc2._queue.qsize() == 0


def test_failed_campaign_reports_error(tmp_path):
    svc = CampaignService(str(tmp_path / "svc")).start()
    cid = svc.submit(_base_spec(scenario="no_such_scenario"))
    assert svc.wait(cid, timeout=60) == CampaignState.FAILED
    assert "no_such_scenario" in svc.status(cid)["error"]
    assert svc.drain(timeout=30)


# -- SIGTERM kill-and-resume through the service process ----------------------


@pytest.mark.slow
def test_sigterm_mid_campaign_then_restart_resumes_bitwise(
    ref_session, tmp_path
):
    """The acceptance criterion end to end: a ``python -m repro.service``
    child is SIGTERM'd while a (long) churn campaign is mid-flight; it
    drains gracefully (exit 0, campaign ``interrupted`` with durable
    checkpoints); a restarted service on the same state dir resumes it to
    a history bitwise-equal to the uninterrupted ``run_streaming()``."""
    n_slots = 60
    spec = _base_spec(
        n_slots=n_slots, modes=_modes_grid(n_slots, N_UES + 1),
        churn=ChurnSchedule(
            n_ue_ids=N_UES + 1, segment_slots=SEG,
            initial=tuple(range(N_UES)),
            events=((5 * SEG, N_UES - 1, "detach"),
                    (10 * SEG, N_UES, "attach")),
        ),
    )
    state = str(tmp_path / "svc")
    env = dict(os.environ)
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    child = subprocess.Popen(
        [sys.executable, "-m", "repro.service", "--state-dir", state,
         "--port", "0", "--max-segment-slots", str(SEG)],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        hello = json.loads(child.stdout.readline())
        base = hello["url"]

        req = urllib.request.Request(
            base + "/campaigns", data=spec.to_json().encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            cid = json.loads(r.read().decode())["campaign_id"]

        # poll until the campaign is provably mid-flight (>= 1 segment
        # done, not finished), then deliver the SIGTERM
        deadline = time.monotonic() + 180
        while True:
            with urllib.request.urlopen(
                base + f"/campaigns/{cid}", timeout=10
            ) as r:
                st = json.loads(r.read().decode())
            if 1 <= st["segments_done"] < st["n_segments"]:
                break
            assert st["state"] not in (
                "completed", "failed", "cancelled"
            ), f"campaign reached {st['state']} before the kill"
            assert time.monotonic() < deadline
            time.sleep(0.01)
        child.send_signal(signal.SIGTERM)
        assert child.wait(timeout=120) == 0, "graceful drain must exit 0"
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()

    # the drained state on disk: interrupted, with a durable checkpoint
    with open(os.path.join(state, "campaigns", cid, "status.json")) as f:
        persisted = json.load(f)
    assert persisted["state"] == CampaignState.INTERRUPTED
    assert 1 <= persisted["segments_done"] < persisted["n_segments"]

    # restart on the same state dir: the campaign is recovered, resumed
    # from its latest checkpoint, and completes bitwise-equal to the
    # uninterrupted run (ai_params training is deterministic, so the
    # parent-trained estimator matches the child's)
    svc = CampaignService(
        state, max_segment_slots=SEG, ai_params=ref_session.ai_params,
    ).start()
    assert svc.status(cid)["state"] in (
        CampaignState.QUEUED, CampaignState.RUNNING
    )
    assert svc.wait(cid, timeout=240) == CampaignState.COMPLETED
    ref = ArchesSession(spec, ai_params=ref_session.ai_params).run_streaming()
    assert_history_equal(svc.result(cid), ref)
    np.testing.assert_array_equal(svc.result(cid).attached, ref.attached)
    assert svc.drain(timeout=30)
