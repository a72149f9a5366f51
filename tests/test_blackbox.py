"""Black-box flight recorder + incident timelines.

Three layers: the ring file itself (framing, wrap, torn-write harvest),
the incident state machine (phase timeline, SLO bars, publish), and the
end-to-end chaos path — a seeded rank kill whose victim's last collective
ops come back via ``state.get_blackbox`` and whose survivors' recoveries
land as phase-stamped incidents in the GCS ledger."""

import re
import time

import pytest

import ray_tpu


@pytest.fixture
def cluster():
    from conftest import ensure_shared_runtime

    yield ensure_shared_runtime()


def _arm_chaos(schedule, trace_file=""):
    from ray_tpu._private import fault_injection
    from ray_tpu._private.config import RayConfig

    RayConfig.set("chaos_schedule", schedule)
    RayConfig.set("chaos_trace_file", trace_file)
    fault_injection.reset()
    fault_injection.refresh()


@pytest.fixture
def own_ring(tmp_path):
    """Detach this process's recorder (if any), lend the test a tiny ring
    in tmp_path, and restore the original recorder state afterwards."""
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu._private.config import RayConfig

    saved = (fr.RECORDING, fr._mm, fr._capacity, fr._cursor, fr._seq,
             fr._path)
    saved_bytes = RayConfig.flight_recorder_bytes
    fr.RECORDING, fr._mm = False, None
    RayConfig.set("flight_recorder_bytes", 1024)  # floor-padded to 568
    try:
        yield fr, str(tmp_path)
    finally:
        fr.shutdown()
        RayConfig.set("flight_recorder_bytes", saved_bytes)
        with fr._lock:
            (fr.RECORDING, fr._mm, fr._capacity, fr._cursor, fr._seq,
             fr._path) = saved


# ------------------------------------------------------------- ring framing

def test_ring_roundtrip_wrap_and_limit(own_ring):
    fr, sdir = own_ring
    assert fr.init_process(sdir, "unit")
    assert fr.RECORDING
    for i in range(200):  # ~30 B/record vs ~1 KiB ring: wraps many times
        fr.record("unit.tick", f"i={i}")
    rows = fr.harvest_for(sdir, "unit")
    assert rows, "harvest found nothing in a freshly written ring"
    seqs = [r["seq"] for r in rows]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the ring keeps the NEWEST writes: the tail must be exactly the last
    # records in order, ending at the final one
    assert rows[-1]["kind"] == "unit.tick"
    assert rows[-1]["detail"] == "i=199"
    assert seqs[-1] - seqs[0] == len(seqs) - 1, \
        "harvested tail has seq gaps (old wrapped records misparsed?)"
    assert len(rows) < 200  # the ring is smaller than the write volume
    assert all(r["kind"] in ("recorder.init", "unit.tick") for r in rows)
    # limit= keeps the newest N
    last3 = fr.harvest_for(sdir, "unit", limit=3)
    assert [r["seq"] for r in last3] == seqs[-3:]


def test_record_takes_a_stamp(own_ring):
    fr, sdir = own_ring
    fr.init_process(sdir, "stamped")
    fr.record("past", "x", ts=123.5)
    fr.record("now")
    past, now = fr.harvest_for(sdir, "stamped")[-2:]
    assert (past["kind"], past["detail"], past["ts"]) == ("past", "x", 123.5)
    assert now["seq"] == past["seq"] + 1 and now["ts"] > 1e9


def test_timed_writes_one_mark_at_its_exit(own_ring):
    fr, sdir = own_ring
    fr.init_process(sdir, "timed")
    with fr.timed("bringup.unit", "why"):
        inside = [r["kind"] for r in fr.harvest_for(sdir, "timed")]
    assert "bringup.unit" not in inside      # one record, at the exit
    with pytest.raises(KeyError):
        with fr.timed("bringup.failed"):     # a phase that raised is marked
            raise KeyError("x")
    fr.mark("bringup.known", 2.5)
    unit, failed, known = fr.harvest_for(sdir, "timed")[-3:]
    # the seconds, the detail, and since PR 68 what the block cost
    seconds, why, cost = unit["detail"].split("|")
    assert unit["kind"] == "bringup.unit" and why == "why"
    assert 0.0 <= float(seconds) < 60.0
    assert [t.split("=")[0] for t in cost.split()] == [
        "cpu", "majflt", "inblock"]
    seconds, _, cost = failed["detail"].partition("|")
    assert failed["kind"] == "bringup.failed" and float(seconds) >= 0
    assert cost.startswith("cpu=")
    # the timeline's detail is the caller's, without the cost
    assert [m[4] for m in fr.bringup_timeline(sdir)[0]
            if m[1] == "bringup.unit"] == ["why"]
    assert (known["kind"], known["detail"]) == ("bringup.known", "2.500000")
    (_, _, start, end, _), = [m for m in fr.bringup_timeline(sdir)[0]
                              if m[1] == "bringup.known"]
    assert end == known["ts"] and end - start == pytest.approx(2.5)


def test_ring_harvest_survives_torn_bytes(own_ring):
    fr, sdir = own_ring
    fr.init_process(sdir, "torn")
    for i in range(10):
        fr.record("k", f"v{i}")
    path = fr.ring_path(sdir, "torn")
    fr.shutdown()
    buf = bytearray(open(path, "rb").read())
    # stomp a byte mid-data-region: at most the torn record is lost, the
    # scan resynchronizes on the next magic
    buf[fr.HEADER.size + 40] ^= 0xFF
    open(path, "wb").write(bytes(buf))
    rows = fr.harvest(path)
    assert len(rows) >= 7
    assert rows[-1]["detail"] == "v9"
    # garbage input never raises
    open(path, "wb").write(b"\x00" * 100)
    assert fr.harvest(path) == []
    assert fr.harvest(path + ".missing") == []


def test_recorder_disabled_by_zero_bytes(own_ring, tmp_path):
    fr, _ = own_ring
    from ray_tpu._private.config import RayConfig

    RayConfig.set("flight_recorder_bytes", 0)
    assert not fr.init_process(str(tmp_path / "off"), "w0")
    assert not fr.RECORDING
    fr.record("dropped", "silently")  # must be a no-op, not an error


# -------------------------------------------------------- incident timeline

def test_incident_phases_sum_to_recovery_and_slo(cluster):
    from ray_tpu._private import incidents
    from ray_tpu._private.config import RayConfig

    published = []
    incidents.set_publisher(published.append)
    saved_slo = RayConfig.recovery_slo
    RayConfig.set("recovery_slo",
                  "collective.detect<15,serve<1, junk, bad<oops")
    try:
        # junk entries are ignored, not fatal
        bars = incidents._slo_bars()
        assert [(b[1], b[2], b[3]) for b in bars] == \
            [("collective", "detect", 15.0), ("serve", "", 1.0)]

        inc = incidents.open_incident(
            "collective", kind="worker_died", detail="g1", victim="rankX")
        inc.stamp("detect")
        inc.stamp("quarantine")
        time.sleep(0.02)
        inc.stamp("rebuild")
        rec = inc.close()
        assert rec is inc.close()  # idempotent
        names = [n for n, _ in rec["phases"]]
        assert names == ["detect", "quarantine", "rebuild", "resume"]
        order = [incidents.PHASES.index(n) for n in names]
        assert order == sorted(order), "phases stamped out of canonical order"
        assert all(s >= 0 for _, s in rec["phases"])
        assert abs(sum(s for _, s in rec["phases"])
                   - rec["recovery_seconds"]) < 1e-9
        assert rec["slo"] == "pass" and len(rec["slo_bars"]) == 1
        assert published == [rec]
        assert incidents.list_local()[-1] is rec

        # a backdated serve incident blows the 1 s whole-recovery bar
        slow = incidents.open_incident(
            "serve", started_mono=time.monotonic() - 2.0).close()
        assert slow["recovery_seconds"] > 1.9
        assert slow["slo"] == "fail"

        # no bar matches this subsystem at all
        assert incidents.observe("task_retry", 0.5)["slo"] == "none"
    finally:
        RayConfig.set("recovery_slo", saved_slo)
        incidents.set_publisher(None)


# ------------------------------------------------- seeded chaos, end to end

@ray_tpu.remote(num_cpus=1)
class _BoxRank:
    """One collective rank per worker process (same shape as test_chaos's
    _ChaosRank, plus: reports its worker id and its recovery incident)."""

    def whoami(self):
        from ray_tpu._private.worker import require_core

        return require_core().worker_id.hex()

    def run(self, rank, world, name, victim, schedule, trace_file):
        import numpy as np

        from ray_tpu.exceptions import CollectiveWorkerDied
        from ray_tpu.util import collective as col
        from ray_tpu.util.collective import collective as ccore

        if rank == victim:
            _arm_chaos(schedule, trace_file)
        col.init_collective_group(world, rank, backend="cpu",
                                  group_name=name)
        data = np.ones(8, dtype=np.float32) * (rank + 1)
        try:
            col.allreduce(data, group_name=name, timeout_s=120)
            return None  # victim never gets here; clean ranks shouldn't
        except CollectiveWorkerDied:
            pass
        g = ccore._groups[name]
        g.rebuild(timeout_s=60)
        col.allreduce(data, group_name=name, timeout_s=60)
        incident = g.last_incident
        col.destroy_collective_group(name)
        return incident


def test_chaos_rank_kill_harvests_blackbox_and_incident(cluster, tmp_path):
    """Rank 3 SIGKILL'd mid-allreduce by the seeded chaos engine: the
    nodelet harvests the victim's ring (its last collective-op records
    reach ``state.get_blackbox``), every survivor's rebuild closes a
    phase-stamped incident whose phases sum to ``recovery_seconds``, and
    the whole run is trace-identical across repeats."""
    from ray_tpu.exceptions import RayActorError, WorkerCrashedError
    from ray_tpu.util import state

    def run_once(tag):
        name = f"bbox-ar-{tag}"
        trace = str(tmp_path / f"bbox_trace_{tag}.log")
        schedule = "seed=7;collective.step=kill@1"
        actors = [_BoxRank.remote() for _ in range(4)]
        victim_hex = ray_tpu.get(actors[3].whoami.remote(), timeout=60)
        refs = [a.run.remote(r, 4, name, 3,
                             schedule if r == 3 else "", trace)
                for r, a in enumerate(actors)]
        with pytest.raises((RayActorError, WorkerCrashedError)):
            ray_tpu.get(refs[3], timeout=180)
        incidents_out = ray_tpu.get(refs[:3], timeout=180)
        for a in actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass

        # --- the victim's black box reaches the GCS with its last ops
        deadline = time.monotonic() + 60
        boxes = []
        while time.monotonic() < deadline:
            boxes = state.get_blackbox(worker_id=victim_hex)
            if boxes:
                break
            time.sleep(0.25)
        assert boxes, f"no blackbox harvested for victim {victim_hex}"
        box = boxes[-1]
        assert box["worker_id"] == victim_hex and box["records"]
        seqs = [r["seq"] for r in box["records"]]
        assert seqs == sorted(seqs)
        ops = [r for r in box["records"]
               if r["kind"] == "col.op" and r["detail"].startswith(name)]
        assert ops, f"victim ring lacks its collective ops: " \
            f"{[r['kind'] for r in box['records']]}"
        assert f"{name}|allreduce|seq=" in ops[-1]["detail"]
        # the chaos firing that killed it is on the record too
        assert any(r["kind"] == "chaos.hit" for r in box["records"])

        # --- every survivor closed a phase-stamped incident
        for rec in incidents_out:
            assert rec and rec["subsystem"] == "collective" and rec["ok"]
            names = [n for n, _ in rec["phases"]]
            order = [["detect", "quarantine", "rebuild", "restore",
                      "resume"].index(n) for n in names]
            assert order == sorted(order), f"non-monotone phases: {names}"
            assert "detect" in names and "rebuild" in names
            assert all(s >= 0 for _, s in rec["phases"])
            assert abs(sum(s for _, s in rec["phases"])
                       - rec["recovery_seconds"]) < 1e-6
            assert rec["recovery_seconds"] < 120

        # --- and published it into the cluster-wide ledger
        deadline = time.monotonic() + 30
        want = {rec["id"] for rec in incidents_out}
        while time.monotonic() < deadline:
            got = {r["id"] for r in state.list_incidents(
                subsystem="collective", limit=1000)}
            if want <= got:
                break
            time.sleep(0.25)
        assert want <= got, f"incidents missing from GCS: {want - got}"
        return open(trace).read()

    t1, t2 = run_once(1), run_once(2)
    assert t1 == t2 == "collective.step[rank3]#1:kill\n"
