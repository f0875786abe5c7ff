"""Dataset tooling tests: SequenceFile round-trip + one-command VOC→.azr.

Covers the reference-format interchange (``RoiByteImageToSeq.scala:33``
record layout inside Hadoop SequenceFiles) and the get_pascal ingest path
(``pipeline/ssd/data/pascal/*.sh`` equivalents).
"""

import os
import sys
import textwrap

import cv2
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from analytics_zoo_tpu.data.records import (
    SSDByteRecord,
    read_ssd_records,
    write_ssd_records,
)
from tools.seqfile_to_azr import (
    decode_reference_record,
    encode_reference_record,
    read_sequence_file,
    read_vint,
    write_sequence_file,
    write_vint,
)
from tools import get_pascal, seqfile_to_azr


def _jpeg(seed=0, w=32, h=24):
    rng = np.random.RandomState(seed)
    ok, buf = cv2.imencode(".jpg", (rng.rand(h, w, 3) * 255).astype(np.uint8))
    assert ok
    return buf.tobytes()


class TestVint:
    def test_roundtrip(self):
        for v in (0, 1, 127, -112, 128, 300, 65535, -129, 2 ** 30, -2 ** 30):
            buf = write_vint(v)
            out, off = read_vint(buf, 0)
            assert out == v, v
            assert off == len(buf)


class TestSequenceFileRoundTrip:
    def test_records_roundtrip_with_sync(self, tmp_path):
        recs = [
            SSDByteRecord(
                data=_jpeg(i), path=f"img{i}.jpg",
                gt=np.asarray([[1 + i % 3, 0, 4, 5, 20, 18],
                               [2, 1, 1, 2, 10, 12]], np.float32))
            for i in range(12)
        ]
        recs.append(SSDByteRecord(data=_jpeg(99), path="empty.jpg",
                                  gt=np.zeros((0, 6), np.float32)))
        seq = str(tmp_path / "part-0.seq")
        write_sequence_file(seq, [encode_reference_record(r) for r in recs],
                            sync_interval=4)  # force sync-escape records
        back = [decode_reference_record(k, v)
                for k, v in read_sequence_file(seq)]
        assert len(back) == len(recs)
        for a, b in zip(recs, back):
            assert b.data == a.data
            assert b.path == os.path.basename(a.path)
            np.testing.assert_allclose(b.gt, a.gt)

    def test_cli_converts_to_azr(self, tmp_path):
        recs = [SSDByteRecord(data=_jpeg(i), path=f"i{i}.jpg",
                              gt=np.asarray([[1, 0, 1, 2, 9, 9]], np.float32))
                for i in range(5)]
        seq = str(tmp_path / "data.seq")
        write_sequence_file(seq, [encode_reference_record(r) for r in recs])
        out_prefix = str(tmp_path / "out")
        assert seqfile_to_azr.main([seq, "-o", out_prefix, "-p", "2"]) == 0
        shards = sorted(str(p) for p in tmp_path.glob("out-*.azr"))
        assert len(shards) == 2
        back = list(read_ssd_records(shards))
        assert len(back) == 5
        assert {b.data for b in back} == {r.data for r in recs}


def _mini_devkit(root, n=4):
    """Synthesize a tiny VOCdevkit 2007 with JPEGs + XML annotations."""
    base = os.path.join(root, "VOC2007")
    for sub in ("Annotations", "JPEGImages", "ImageSets/Main"):
        os.makedirs(os.path.join(base, sub), exist_ok=True)
    ids = []
    for i in range(n):
        img_id = f"{i:06d}"
        ids.append(img_id)
        with open(os.path.join(base, "JPEGImages", img_id + ".jpg"), "wb") as f:
            f.write(_jpeg(i, w=48, h=36))
        xml = textwrap.dedent(f"""\
            <annotation>
              <size><width>48</width><height>36</height><depth>3</depth></size>
              <object><name>dog</name><difficult>0</difficult>
                <bndbox><xmin>{4 + i}</xmin><ymin>5</ymin>
                        <xmax>{20 + i}</xmax><ymax>30</ymax></bndbox>
              </object>
            </annotation>""")
        with open(os.path.join(base, "Annotations", img_id + ".xml"), "w") as f:
            f.write(xml)
    with open(os.path.join(base, "ImageSets", "Main", "trainval.txt"), "w") as f:
        f.write("\n".join(ids) + "\n")


class TestGetPascal:
    def test_devkit_to_shards(self, tmp_path):
        devkit = str(tmp_path / "VOCdevkit")
        _mini_devkit(devkit)
        out = str(tmp_path / "azr" / "voc")
        rc = get_pascal.main(["--devkit", devkit, "-o", out,
                              "--sets", "voc_2007_trainval", "-p", "2"])
        assert rc == 0
        shards = sorted((tmp_path / "azr").glob("*.azr"))
        assert len(shards) == 2
        back = list(read_ssd_records([str(s) for s in shards]))
        assert len(back) == 4
        assert all(b.gt.shape == (1, 6) for b in back)
        assert all(b.gt[0, 0] == 12.0 for b in back)  # dog class id


class TestReportHelper:
    def test_append_report_and_command(self, tmp_path, monkeypatch):
        import json

        from analytics_zoo_tpu.utils.report import (append_report,
                                                    reconstruct_command)

        monkeypatch.setattr("sys.argv",
                            ["x.py", "--epochs", "3", "--out", "f.md",
                             "--flag"])
        cmd = reconstruct_command("examples/x.py")
        assert cmd == "python examples/x.py --epochs 3 --flag"
        out = tmp_path / "acc.md"
        append_report(str(out), "T", "examples/x.py", {"a": 1})
        text = out.read_text()
        assert "## T" in text and json.loads(
            text.split("```json\n")[1].split("```")[0]) == {"a": 1}


class TestMemorySummary:
    def test_memory_summary_runs(self):
        from analytics_zoo_tpu.utils.profiling import memory_summary

        out = memory_summary()
        assert isinstance(out, dict) and len(out) >= 1
        for stats in out.values():
            assert isinstance(stats, dict)


class TestChaosDrillHelpers:
    """Fast pieces of tools/chaos_drill.py (the full drill is the
    committed RESILIENCE_r01.json execution)."""

    def test_schedule_is_seeded_deterministic(self):
        import random

        from tools.chaos_drill import build_schedule

        a = build_schedule(random.Random(7))
        b = build_schedule(random.Random(7))
        assert [(f.kind, f.at_batch) for f in a] == \
               [(f.kind, f.at_batch) for f in b]
        kinds = {f.kind for f in a}
        assert {"sigterm", "mid_save_kill", "stall", "corrupt_latest",
                "xla_transient", "crash"} <= kinds
        # corruption is always followed by its fallback-forcing crash
        assert a[-2].kind == "corrupt_latest"
        assert a[-1] == type(a[-1])("crash", a[-2].at_batch + 1)

    def test_shard_read_drill_survives(self, tmp_path):
        import random

        from tools.chaos_drill import shard_read_drill

        out = shard_read_drill(str(tmp_path), random.Random(0))
        assert out["survived"] is True
        assert out["retries"] == out["injected_transient_errors"] == 2
        assert out["skipped_records"] == 1
        assert out["records_read"] == out["records_written"] - 1


class TestAnomalyDrillHelpers:
    """Fast pieces of the r02 anomaly ladder drill (the full drill is
    the committed RESILIENCE_r02.json execution)."""

    def test_anomaly_schedule_seeded_deterministic(self):
        import random

        from tools.chaos_drill import build_anomaly_schedule

        a = build_anomaly_schedule(random.Random(5), rollback_after=3)
        b = build_anomaly_schedule(random.Random(5), rollback_after=3)
        assert [(f.kind, f.at_batch, f.batches) for f in a] == \
               [(f.kind, f.at_batch, f.batches) for f in b]
        kinds = [f.kind for f in a]
        assert kinds == ["nan_grads", "nan_grads", "corrupt_batch"]
        # one isolated batch, one exactly-K burst, one persistent window
        assert a[0].batches == 1 and a[1].batches == 3
        assert a[2].batches > 100
        # windows are disjoint and ordered
        assert a[0].at_batch < a[1].at_batch
        assert a[1].at_batch + a[1].batches <= a[2].at_batch

    def test_replay_batches_contract(self):
        import numpy as np

        from analytics_zoo_tpu.data.dataset import DataSet
        from analytics_zoo_tpu.data.parallel import replay_batches
        from analytics_zoo_tpu.resilience.anomaly import batch_fingerprint

        rng = np.random.RandomState(0)
        X = rng.randn(24, 4).astype(np.float32)
        Y = rng.randn(24, 1).astype(np.float32)

        def fresh():
            return (DataSet.from_arrays(input=X, target=Y)
                    .batch(8).parallel(0, base_seed=3))

        # live pass over epoch 0 then epoch 1
        loader = fresh()
        epochs = [list(loader), list(loader)]
        assert loader.last_epoch == 1
        for ep in (0, 1):
            got = replay_batches(fresh(), ep, [0, 2])
            for i in (0, 2):
                assert batch_fingerprint(got[i]) == \
                    batch_fingerprint(epochs[ep][i]), (ep, i)
        with pytest.raises(ValueError, match="ended before"):
            replay_batches(fresh(), 0, [99])


class TestIngestRealFixture:
    def test_smoke_alexnet_end_to_end(self, tmp_path):
        """Satellite: wire tools/ingest_real.py into the suite — the
        reduced (SSD-AlexNet) smoke runs devkit→get_pascal→shards→train
        →VOC07-mAP in-process; the committed REAL_DATA.json is the
        banked SSD-VGG execution of the same command."""
        import json

        from tools import ingest_real

        out = str(tmp_path / "REAL_DATA.json")
        rc = ingest_real.main(["--smoke", "--arch", "alexnet",
                               "--batch", "8", "--epochs", "1",
                               "--num-shards", "2", "--out", out])
        assert rc == 0
        report = json.load(open(out))
        assert report["smoke"] is True and report["arch"] == "alexnet"
        assert any("voc_2007_trainval: 16 records" in line
                   for line in report["conversion"])
        assert report["train"]["epochs"] == 1
        assert 0.0 <= report["train"]["map_voc07"] <= 1.0
        assert report["train"]["images"] == 8
        # scratch paths are scrubbed from the artifact
        assert "<tmp>" in report["conversion"][0]


class TestServeDrillHelpers:
    """tools/serve_drill.py (the committed artifact is the full-size
    RESILIENCE_r03.json execution; the smoke drill here runs the whole
    burst -> shed -> degrade -> crash -> failover -> recover story in a
    few seconds of virtual time)."""

    def test_arrival_script_seeded_and_burst_shaped(self):
        import random

        from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec
        from tools.serve_drill import build_arrival_script

        def build():
            monkey = ChaosMonkey([FaultSpec(
                "burst_load", 100, batches=150, detail={"rate_x": 4.0})])
            return build_arrival_script(random.Random(3), True, monkey)

        (a, burst_a), (b, burst_b) = build(), build()
        assert a == b and burst_a == burst_b      # seeded deterministic
        assert burst_a["from_index"] == 100
        assert burst_a["requests_in_window"] == 150
        # arrival instants are monotone absolute times, and the burst
        # window really runs ~4x hotter than the surrounding load
        ts = [t for t, _ in a]
        assert ts == sorted(ts)
        pre = ts[99] - ts[0]                      # 100 normal gaps
        burst = ts[249] - ts[99]                  # 150 burst gaps
        assert (pre / 100) / (burst / 150) > 2.0

    def test_smoke_drill_all_checks_pass(self):
        from tools.serve_drill import serving_drill

        out = serving_drill(seed=0, smoke=True)
        assert out["checks"]["ok"], out["checks"]
        # the hard invariants, re-asserted explicitly: nothing lost,
        # and shedding+degradation beat the no-shedding baseline
        assert out["baseline_no_shedding"]["accounting"]["unaccounted"] == 0
        assert out["drill"]["accounting"]["unaccounted"] == 0
        assert (out["miss_rate"]["shedding_plus_degradation"]
                < out["miss_rate"]["baseline_no_shedding"])


class TestServeFleetDrill:
    """tools/serve_fleet_drill.py (ISSUE 14): the multiplexed fleet +
    closed-loop autoscaler smoke, and the committed million-request
    SERVING_SCALE_r01.json artifact's claims."""

    def test_smoke_drill_mechanics_and_conservation(self):
        from tools.serve_fleet_drill import fleet_drill

        out = fleet_drill(seed=0, smoke=True)
        assert out["checks"]["ok"], out["checks"]
        # the hard invariants, re-asserted explicitly
        assert out["static_pool"]["accounting"]["unaccounted"] == 0
        assert out["autoscaled"]["accounting"]["unaccounted"] == 0
        assert (out["static_pool"]["accounting"]["submitted"]
                == out["autoscaled"]["accounting"]["submitted"]
                == out["config"]["n_requests"])
        # every scenario replayed byte-identically from the seed
        for arm in (out["static_pool"], out["autoscaled"],
                    out["prewarm_subphase"]["on"],
                    out["prewarm_subphase"]["off"]):
            assert arm["replay"]["replay_identical"] is True
        # the closed loop actuated, growth was pre-warmed, and the
        # cold arm of the sub-phase really paid the compile tax
        assert out["autoscaled"]["autoscale"]["grows"] >= 1
        assert out["prewarm_subphase"]["on"]["pool"]["cold_compiles"] == 0
        assert out["prewarm_subphase"]["off"]["pool"]["cold_compiles"] > 0
        # ISSUE 17: the recommendation family (DedupEmbed lookup tower)
        # multiplexes in the smoke fleet and actually serves traffic
        assert "rec" in out["config"]["model_mix"]
        rec = out["static_pool"]["per_model"]["rec"]
        assert rec["completed"] > 0

    def test_committed_fleet_artifact_banks_the_scale_claims(self):
        """The committed full-scale artifact's own claims (strict —
        the smoke relaxations never apply to it): ~1M requests per arm
        at equal trace, requests conserved in both arms, autoscaled
        goodput > static with strictly lower miss rate, the pre-warm
        on/off sub-phase present with the cold-compile tax banked, and
        byte-identical replay throughout."""
        import json

        from tools.check_artifacts import LEGACY, PATTERN, REQUIRED_KEYS

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "SERVING_SCALE_r01.json")
        report = json.load(open(path))
        assert report["verdict"] == "PASS" and report["checks"]["ok"]
        assert report["smoke"] is False
        cfg = report["config"]
        assert cfg["n_requests"] >= 900_000
        static, auto = report["static_pool"], report["autoscaled"]
        # equal trace, both arms, nothing lost
        assert (static["accounting"]["submitted"]
                == auto["accounting"]["submitted"]
                == cfg["n_requests"])
        assert static["accounting"]["unaccounted"] == 0
        assert auto["accounting"]["unaccounted"] == 0
        assert cfg["trace_sha256"]
        # the headline: goodput up, miss rate strictly down, at equal
        # offered load
        assert auto["goodput_rps"] > static["goodput_rps"]
        assert (auto["deadline_miss_rate"]
                < static["deadline_miss_rate"])
        assert report["headline"]["goodput_gain"] > 1.0
        # the loop actuated both directions and growth pre-warmed
        assert auto["autoscale"]["grows"] >= 1
        assert auto["autoscale"]["shrinks"] >= 1
        assert auto["pool"]["max"] > auto["pool"]["initial"]
        assert auto["pool"]["cold_compiles"] == 0
        # pre-warm sub-phase: the tax exists and pre-warm deletes it
        sub = report["prewarm_subphase"]
        assert sub["off"]["pool"]["cold_compiles"] > 0
        assert sub["on"]["pool"]["cold_compiles"] == 0
        assert sub["cold_compile_tax_s"] > 0
        assert (sub["on"]["deadline_miss_rate"]
                <= sub["off"]["deadline_miss_rate"])
        # replay discipline (the OBS_r02 standard)
        for arm in (static, auto, sub["on"], sub["off"]):
            assert arm["replay"]["replay_identical"] is True
        # governed by the artifact lint as STAMPED, not grandfathered
        assert PATTERN.match("SERVING_SCALE_r01.json")
        assert "SERVING_SCALE_r01.json" not in LEGACY
        meta = report["run_metadata"]
        assert all(k in meta for k in REQUIRED_KEYS)

    def test_cli_smoke_writes_stamped_artifact(self, tmp_path):
        import json

        import tools.serve_fleet_drill as fd

        out = tmp_path / "SERVING_SCALE_smoke.json"
        rc = fd.main(["--smoke", "--out", str(out), "--seed", "0"])
        assert rc == 0
        report = json.loads(out.read_text())
        assert report["verdict"] == "PASS"
        assert "run_metadata" in report


class TestElasticMeshDrill:
    """ISSUE 19: the committed ELASTIC_r01.json artifact's claims (the
    full drill SIGTERMs a width-4 run and resumes at widths 2/4/8 in
    fresh processes — the smoke re-execution rides the slow lane), and
    the serving width-vs-count reshape segment in tier-1."""

    def test_committed_elastic_artifact_banks_the_claims(self):
        import json

        from tools.check_artifacts import LEGACY, PATTERN, REQUIRED_KEYS

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "ELASTIC_r01.json")
        report = json.load(open(path))
        assert report["verdict"] == "PASS"
        tr = report["training"]
        assert tr["ok"] and all(tr["checks"].values()), tr["checks"]
        assert tr["save_width"] == 4
        assert sorted(tr["resume_widths"]) == [2, 4, 8]
        # the honest bit-exactness pins: same-width resume is byte-
        # identical (params sha256), placement preserves bytes at every
        # width, and the loader re-seek is shard-count independent
        assert (tr["resume"]["w4"]["params_sha256"]
                == tr["reference"]["w4"]["params_sha256"])
        for leg in list(tr["resume"].values()) + [tr["resume_w2_4workers"]]:
            probe = leg["placement_probe"]
            assert probe["raw_sha256"] == probe["placed_sha256"]
        assert (tr["resume"]["w2"]["params_sha256"]
                == tr["resume_w2_4workers"]["params_sha256"])
        # cross-width: exact step completion, fp deltas at ulp scale —
        # zero at the save width, nonzero-but-tiny across widths
        # (XLA's per-width reduction order; see the artifact policy)
        deltas = tr["fingerprint_delta_vs_reference"]
        assert deltas["w4"] == 0.0
        fp = abs(float(tr["reference"]["w4"]["fingerprint"]))
        assert all(d <= 1e-4 * fp for d in deltas.values())
        # the checkpoint meta carried the elastic coordinates
        assert tr["resume"]["w2"]["resumed_from"]["world_width"] == 4
        assert "samples_in_epoch" in tr["resume"]["w2"]["resumed_from"]
        # serving half: at least one width-reshape, replay-identical
        seg = report["serving_reshape_segment"]
        assert seg["checks"]["ok"], seg["checks"]
        reshapes = seg["summary"]["reshapes"]
        assert len(reshapes) >= 1
        assert reshapes[0]["to_width"] == 4
        assert "B/128" in reshapes[0]["rationale"]
        assert seg["summary"]["replay"]["replay_identical"] is True
        assert (seg["summary"]["devices_used"]
                <= seg["config"]["autoscale_policy"]["device_budget"])
        # governed by the artifact lint as STAMPED, not grandfathered
        assert PATTERN.match("ELASTIC_r01.json")
        assert "ELASTIC_r01.json" not in LEGACY
        meta = report["run_metadata"]
        assert all(k in meta for k in REQUIRED_KEYS)

    def test_reshape_segment_smoke(self):
        """The width-vs-count segment end-to-end on the virtual clock:
        the saturated model reshapes onto width-4 slices with the
        occupancy rationale, later growth respects the device budget,
        and the replay is byte-identical."""
        from tools.serve_fleet_drill import reshape_segment

        out = reshape_segment(seed=0, smoke=True)
        assert out["checks"]["ok"], out["checks"]
        s = out["summary"]
        assert s["model_width_final"]["fraud"] == 4
        assert s["reshapes"][0]["fill"] >= 0.8
        assert s["accounting"]["unaccounted"] == 0

    def test_fleet_drill_reshape_knobs_default_off(self):
        """Byte-inertness: the default fleet drill scenarios never
        reshape — their summaries carry NO slice keys, so the banked
        SERVING_SCALE_r01 replay digests are untouched."""
        from tools.serve_fleet_drill import (build_model_set, build_trace,
                                             run_twice)

        configs = build_model_set(0)
        trace = build_trace(0, 2000, 2000 / 450.0, burst=True)
        summary, replay = run_twice(trace, configs, autoscale=True,
                                    n_replicas=2)
        assert replay["replay_identical"] is True
        assert "reshapes" not in summary
        assert "model_width_final" not in summary
        assert "reshapes" not in summary["autoscale"]

    @pytest.mark.slow
    def test_elastic_drill_smoke_execution(self, tmp_path):
        """Re-execute the training half end-to-end (8 subprocess legs):
        the same checks the committed artifact banked must hold on a
        fresh run."""
        import tools.bench_scaling as bs

        class _Args:
            virtual = True

        def env_for(n):
            env = dict(os.environ)
            env["PYTHONPATH"] = bs._REPO + (
                os.pathsep + env["PYTHONPATH"]
                if env.get("PYTHONPATH") else "")
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={n}"
            return env

        out = bs.run_elastic_drill(_Args(), env_for)
        assert out["ok"], out.get("checks", out.get("error"))


class TestLiveSwapDrill:
    """tools/live_swap_drill.py (ISSUE 18): the hot-swap + canary +
    rollback day under chaos, and the committed LIVE_SWAP_r01.json
    artifact's claims.  The committed artifact pins the banked run in
    tier-1; the live smoke re-executes the whole day and rides the
    slow lane (the TestBenchScalingDrill precedent)."""

    @pytest.mark.slow
    def test_cli_smoke_drill_mechanics_and_conservation(self, tmp_path):
        """One smoke execution through the CLI covers the drill
        mechanics: rollouts complete under live traffic, the poisoned
        canary trips and rolls back, chaos fires mid-rollout, sessions
        replay exactly, and nothing is lost."""
        import json

        import tools.live_swap_drill as lsd

        out = tmp_path / "LIVE_SWAP_smoke.json"
        rc = lsd.main(["--smoke", "--out", str(out), "--seed", "0"])
        assert rc == 0
        report = json.loads(out.read_text())
        assert report["verdict"] == "PASS"
        assert report["checks"]["ok"], report["checks"]
        s = report["scenario"]
        # the hard invariants, re-asserted explicitly
        assert s["accounting"]["unaccounted"] == 0
        assert s["failed"] == 0 and s["shed_total"] == 0
        assert s["swap"]["completed"] >= 3
        assert s["swap"]["trips"] == 1 and s["swap"]["rollbacks"] == 1
        assert s["swap"]["poison_reverted_replicas"] == []
        assert s["swap"]["lkg_promotions"] >= 1
        assert s["sessions"]["transcripts_exact"] is True
        assert s["chaos"]["failovers"] >= 2
        assert s["conservation"]["ok"] is True
        assert s["replay"]["replay_identical"] is True
        assert "run_metadata" in report

    def test_committed_live_swap_artifact_banks_the_claims(self):
        """The committed full-scale artifact's own claims (strict — the
        smoke relaxations never apply): a 48k-request day, >= 3
        completed hot-swaps under live traffic with zero dropped
        requests, the one poisoned publish tripped the canary and
        rolled back with zero poisoned outputs served, serve-LKG
        promoted, chaos mid-rollout failed over and the rollout still
        completed, session transcripts exact, spans conserved, and the
        whole day byte-identical on replay."""
        import json

        from tools.check_artifacts import LEGACY, PATTERN, REQUIRED_KEYS

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "LIVE_SWAP_r01.json")
        report = json.load(open(path))
        assert report["verdict"] == "PASS" and report["checks"]["ok"]
        assert report["smoke"] is False
        assert report["config"]["n_requests"] >= 45_000
        s = report["scenario"]
        acct = s["accounting"]
        assert acct["unaccounted"] == 0
        assert acct["by_state"].get("done", 0) == acct["submitted"]
        assert s["failed"] == 0 and s["shed_total"] == 0
        # >= 3 completed rollouts, exactly one poisoned trip+rollback
        sw = s["swap"]
        assert sw["completed"] >= 3
        assert sw["trips"] == 1 and sw["rollbacks"] == 1
        rolled = [h for h in sw["history"]
                  if h["outcome"] == "rolled_back"]
        assert len(rolled) == 1
        assert "canary_trip" in rolled[0]["reason"]
        assert sw["poison_reverted_replicas"] == []
        # serve-LKG promoted from the clean rollouts
        assert sw["lkg_promotions"] >= 1
        assert "fraud" in s["serve_lkg_tiers"]
        # session-pinned replicas swapped last, transcripts exact
        assert s["sessions"]["transcripts_exact"] is True
        assert s["sessions"]["failed"] == 0
        assert any(v["pinned"] for v in sw["rollout_orders"].values())
        # chaos mid-rollout: both kinds fired, batches failed over,
        # and that rollout still completed
        assert set(s["chaos"]["fired"]) >= {"replica_crash",
                                            "slow_forward"}
        assert s["chaos"]["failovers"] >= 2
        # swap lifecycle in the flight recording + span conservation
        assert {"swap_started", "swap_rolling", "swap_complete",
                "canary_trip", "swap_rollback",
                "swap_lkg_promoted"} <= set(sw["note_kinds"])
        assert s["conservation"]["ok"] is True
        assert s["recording"]["dropped"] == 0
        # replay discipline (the OBS_r02 standard)
        assert s["replay"]["replay_identical"] is True
        # governed by the artifact lint as STAMPED, not grandfathered
        assert PATTERN.match("LIVE_SWAP_r01.json")
        assert "LIVE_SWAP_r01.json" not in LEGACY
        meta = report["run_metadata"]
        assert all(k in meta for k in REQUIRED_KEYS)


class TestObsDrillHelpers:
    """Fast pieces of tools/obs_drill.py (the committed OBS_r01.json is
    the full-size execution: drill-scale flight recording + replay
    hash)."""

    def test_traced_scenario_span_conservation_smoke(self):
        from analytics_zoo_tpu.obs import span_conservation
        from tools.obs_drill import traced_scenario

        rt, obs, n_script = traced_scenario(seed=0, smoke=True)
        acct = rt.accounting()
        cons = span_conservation(obs.recorder.events())
        # the spine's hard invariants at smoke scale: every request is
        # one rooted trace, nothing dropped from the ring, and the root
        # statuses reconcile exactly with the runtime's own accounting
        assert cons["ok"], cons["violations"]
        assert obs.recorder.dropped == 0
        assert cons["traces"] == acct["submitted"] >= n_script
        assert cons["roots_by_status"] == acct["by_state"]

    def test_committed_obs_artifact_passes_its_own_checks(self):
        import json

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "OBS_r01.json")
        report = json.load(open(path))
        assert report["verdict"] == "PASS" and report["checks"]["ok"]
        assert report["serve_trace"]["replay_identical"] is True
        assert report["serve_trace"]["events_dropped"] == 0


class TestCheckArtifacts:
    """Satellite: the committed-artifact lint runs in tier-1 — a stale,
    hand-edited, or unstamped new artifact fails the suite."""

    def test_repo_artifacts_all_parse_and_new_ones_are_stamped(self):
        from tools.check_artifacts import check_artifacts

        root = os.path.join(os.path.dirname(__file__), os.pardir)
        assert check_artifacts(root) == []

    def test_unstamped_or_unparseable_artifact_fails(self, tmp_path):
        from tools import check_artifacts as ca

        (tmp_path / "NEW_r09.json").write_text('{"no": "metadata"}\n')
        (tmp_path / "OBS_r99.json").write_text("{truncated\n")
        (tmp_path / "PARTIAL_r01.json").write_text(
            '{"run_metadata": {"tool": "x"}}\n')
        (tmp_path / "unrelated.json").write_text("{not linted")
        problems = ca.check_artifacts(str(tmp_path))
        assert len(problems) == 3
        assert any("NEW_r09" in p and "missing run_metadata" in p
                   for p in problems)
        assert any("OBS_r99" in p and "parse" in p for p in problems)
        assert any("PARTIAL_r01" in p and "missing keys" in p
                   for p in problems)
        assert ca.main(["--root", str(tmp_path)]) == 1

    def test_legacy_artifacts_are_grandfathered_but_must_parse(
            self, tmp_path):
        from tools import check_artifacts as ca

        (tmp_path / "RESILIENCE_r01.json").write_text('{"old": true}\n')
        assert ca.check_artifacts(str(tmp_path)) == []
        (tmp_path / "RESILIENCE_r01.json").write_text("{broken")
        assert len(ca.check_artifacts(str(tmp_path))) == 1

    def test_issue9_artifacts_are_stamped_not_grandfathered(self):
        """ISSUE 9 satellite: the MULTICHIP_r06 banking is covered by
        the lint as a STAMPED artifact — the LEGACY set stayed closed
        (adding it there would have silently waived the metadata
        requirement)."""
        import json

        from tools.check_artifacts import LEGACY, PATTERN, REQUIRED_KEYS

        root = os.path.join(os.path.dirname(__file__), os.pardir)
        name = "MULTICHIP_r06.json"
        assert PATTERN.match(name)
        assert name not in LEGACY, f"{name} must not be grandfathered"
        doc = json.load(open(os.path.join(root, name)))
        meta = doc["run_metadata"]
        assert all(k in meta for k in REQUIRED_KEYS)

    def test_committed_multichip_r06_banks_sweeps_and_drill(self):
        """The r06 artifact's own claims hold: both model sweeps have a
        reading per device count with per-window values, and the
        preemption drill resumed to a bit-exact fingerprint from a
        MID-EPOCH checkpoint coordinate."""
        import json

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "MULTICHIP_r06.json")
        doc = json.load(open(path))
        assert doc["virtual"] is True           # labeled honestly
        for model in ("ssd", "ds2"):
            sweep = doc["sweeps"][model]
            assert [r["n"] for r in sweep] == doc["devices"]
            assert all(len(r["windows"]) >= 2 for r in sweep)
        drill = doc["drill"]
        assert drill["ok"] is True
        assert drill["fingerprint_match_bitexact"] is True
        assert drill["loader_coordinates"]["mid_epoch"] is True
        assert drill["resume"]["steps"] == drill["reference"]["steps"]


class TestBenchScalingDrill:
    """Slow-lane live smoke of the ISSUE-9 scaling harness (the
    committed MULTICHIP_r06.json pins the banked run in tier-1; this
    re-executes the preemption-resume machinery end to end)."""

    @pytest.mark.slow
    def test_preemption_resume_drill_bitexact(self):
        import json
        import subprocess
        import sys

        repo = os.path.join(os.path.dirname(__file__), os.pardir)
        out = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "bench_scaling.py"),
             "--devices", "2", "--virtual", "--drill", "--models", "ssd",
             "--steps", "1", "--windows", "1", "--batch-per-chip", "1",
             "--sweep-log", ""],
            capture_output=True, text=True, cwd=repo, timeout=900)
        assert out.returncode == 0, out.stderr[-800:]
        drill = [json.loads(ln) for ln in out.stdout.splitlines()
                 if ln.startswith('{"drill"')][-1]["drill"]
        assert drill["ok"] is True
        assert drill["fingerprint_match_bitexact"] is True


class TestAzTrace:
    """tools/az_trace.py: the SLO-driven drill smoke, the committed
    OBS_r02.json, and the regression sentinel (self-diff clean, a
    doctored baseline flagged)."""

    def test_smoke_drill_all_checks_pass(self):
        from tools.az_trace import az_trace_drill

        result = az_trace_drill(seed=0, smoke=True)
        assert result["checks"]["ok"], result["checks"]
        # the load-bearing pieces individually, for a readable failure
        assert result["checks"]["critical_path_conservation_ok"]
        assert result["checks"]["fast_window_trip_happened"]
        assert result["checks"]["trip_drove_ladder_step_down"]
        assert result["checks"]["replay_byte_identical_from_seed"]
        assert result["tail_attribution"]["dominant_segment"]

    def test_committed_obs_r02_passes_its_own_checks_and_is_stamped(self):
        import json

        from tools.check_artifacts import LEGACY, PATTERN, REQUIRED_KEYS

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "OBS_r02.json")
        report = json.load(open(path))
        assert report["verdict"] == "PASS" and report["checks"]["ok"]
        assert report["serve_trace"]["replay_identical"] is True
        assert report["checks"]["analysis_replay_identical"] is True
        assert report["slo"]["decisions"] > 0
        assert sum(report["slo"]["trips"].values()) >= 1
        downs = [e for e in report["ladder"]["transitions"]
                 if e["kind"] == "tier_down"]
        assert downs and downs[0]["slo_burning"]
        assert report["critical_path_conservation"]["violations"] == []
        # covered by the artifact lint as STAMPED, not grandfathered
        assert PATTERN.match("OBS_r02.json")
        assert "OBS_r02.json" not in LEGACY
        meta = report["run_metadata"]
        assert all(k in meta for k in REQUIRED_KEYS)

    def test_sentinel_self_diff_is_clean(self, tmp_path):
        """baseline vs itself: the seeded drill is deterministic, so a
        fresh run diffed against a just-banked smoke baseline must be
        CLEAN (exit 0) — the sentinel only fires when code changes the
        tail."""
        import json

        import tools.az_trace as az

        result = az.az_trace_drill(seed=0, smoke=True)
        baseline = {"drill": "az_trace", "seed": 0, "smoke": True,
                    **result}
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps(baseline))
        code, regressions = az.run_sentinel(str(path))
        assert code == 0 and regressions == [], regressions

    def test_sentinel_flags_a_doctored_baseline(self):
        """Shrink the baseline's tail numbers: the (unchanged) fresh
        report now reads as a regression on exactly the doctored
        axes."""
        import copy
        import json

        from tools.az_trace import az_trace_drill, sentinel_diff

        fresh = az_trace_drill(seed=0, smoke=True)
        baseline = copy.deepcopy(json.loads(json.dumps(fresh)))
        baseline["tail_attribution"]["percentiles"]["p99_s"] /= 2.0
        seg = baseline["tail_attribution"]["segments"]["queue_wait"]
        seg["p99_mean_s"] /= 2.0
        baseline["slo"]["peak_burns"]["shed-rate"]["fast"] /= 2.0
        regressions = sentinel_diff(baseline, fresh)
        text = "\n".join(regressions)
        assert "p99 latency" in text
        assert "segment queue_wait" in text
        assert "peak fast burn [shed-rate]" in text
        # and the un-doctored twin stays clean
        assert sentinel_diff(fresh, fresh) == []

    def test_cli_drill_and_query_modes(self, tmp_path):
        """End-to-end CLI: --drill writes a stamped artifact +
        flight JSONL; the query modes run over that recording."""
        import json

        import tools.az_trace as az

        out = tmp_path / "OBS_smoke.json"
        flight = tmp_path / "flight.jsonl"
        rc = az.main(["--drill", "--smoke", "--out", str(out),
                      "--flight-out", str(flight)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert report["verdict"] == "PASS"
        assert "run_metadata" in report
        assert flight.exists()
        # query modes over the dumped recording
        assert az.main(["--flight", str(flight), "--attribute",
                        "--slo-report"]) == 0
        done_trace = None
        for line in flight.read_text().splitlines():
            e = json.loads(line)
            if e.get("kind") == "span" and e.get("parent") is None \
                    and e.get("status") == "done":
                done_trace = e["trace"]
                break
        assert done_trace is not None
        assert az.main(["--flight", str(flight), "--critical-path",
                        done_trace]) == 0


class TestSdcDrillArtifact:
    """ISSUE 20: the committed SDC_r01.json artifact's claims (the full
    drill injects a single bit-flip into one replica's audit view
    mid-epoch, detects it by cross-replica parity within one audit
    interval, evicts the device, resumes checkpoint-free from the LKG
    tier at width 2 with finals matching the fault-free reference, and
    quarantines a slow serving device after EWMA hysteresis)."""

    def test_committed_sdc_artifact_banks_the_claims(self):
        import json

        from tools.check_artifacts import LEGACY, PATTERN, REQUIRED_KEYS

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "SDC_r01.json")
        report = json.load(open(path))
        assert report["verdict"] == "PASS"
        sdc = report["sdc_training"]
        assert sdc["checks"]["ok"] and all(sdc["checks"].values()), \
            sdc["checks"]
        det, cfg = sdc["detection"], sdc["config"]
        # the detection-latency bound: strictly within one audit interval
        assert 0 < det["latency_steps"] <= cfg["audit_every"]
        # the parity vote named exactly the injected replica — one
        # diverging fingerprint, held by the suspect alone
        assert det["suspect"] == sdc["fault"]["replica"]
        assert det["minority"] == [det["suspect"]]
        fps = det["fingerprints"]
        assert len(fps) == cfg["world_width"]
        assert len(set(fps)) == 2
        assert fps.count(fps[det["suspect"]]) == 1
        # checkpoint-free recovery: LKG tier, width 4 -> 2
        res = sdc["resume"]
        assert res["from_tier"] == "lkg"
        assert res["saved_world_width"] == 4
        assert res["resumed_world_width"] == 2
        assert sdc["eviction"]["evicted_device"] == det["suspect"]
        fin = sdc["finals"]
        assert fin["iterations_faulted"] == fin["iterations_reference"]
        assert fin["params_max_abs_diff"] <= \
            cfg["rel_tol"] * max(fin["params_ref_max_abs"], 1e-6)
        # fault-free arm: a full run of audits with ZERO false positives
        ff = sdc["sentinel_fault_free"]
        assert ff["audits"] > 0
        assert ff["audit_divergences"] == 0 and ff["quarantines"] == 0
        # straggler serving half: flag exactly at the hysteresis ladder,
        # drain-then-retire, device budget decremented once
        st = report["straggler_serving"]
        assert st["checks"]["ok"] and all(st["checks"].values()), \
            st["checks"]
        assert st["flag_events"][0]["streak"] == \
            st["config"]["policy"]["flag_after"]
        q = st["quarantine_events"][0]
        assert q["reason"] == "straggler"
        assert q["device_budget"] == st["config"]["device_budget"] - 1
        assert st["retire_events"][0]["replica"] == q["replica"]
        assert st["sentinel_fault_free"]["straggler_flags"] == 0
        assert st["accounting"]["unaccounted"] == 0
        # replay determinism: both segments re-ran byte-identically
        rep = report["replay"]
        assert rep["sdc_identical"] is True
        assert rep["straggler_identical"] is True
        assert len(rep["sdc_digest"]) == len(rep["straggler_digest"]) == 64
        assert report["fault_kinds_survived"] == ["bit_flip", "slow_device"]
        # governed by the artifact lint as STAMPED, not grandfathered
        assert PATTERN.match("SDC_r01.json")
        assert "SDC_r01.json" not in LEGACY
        meta = report["run_metadata"]
        assert all(k in meta for k in REQUIRED_KEYS)

    def test_chaos_matrix_covers_every_kind(self):
        """The all-kinds-survived claim spans the FULL ``KINDS`` tuple:
        every chaos kind is exercised by a banked drill artifact or by
        the in-process injection probe below.  Adding a kind to KINDS
        without drill coverage fails here."""
        import json

        from analytics_zoo_tpu.resilience.chaos import KINDS, mutate_batch

        root = os.path.join(os.path.dirname(__file__), os.pardir)
        banked = set()
        for name in ("RESILIENCE_r02.json", "SDC_r01.json"):
            with open(os.path.join(root, name)) as f:
                banked |= set(json.load(f)["fault_kinds_survived"])
        with open(os.path.join(root, "RESILIENCE_r03.json")) as f:
            banked |= {s["kind"] for s in json.load(f)["fault_schedule"]}
        # inf_loss rides the in-graph anomaly ladder (test_anomaly.py's
        # end-to-end run); back the matrix claim with the injection
        # itself firing here, not just a listing
        batch = {"input": np.zeros((2, 2), np.float32),
                 "target": np.zeros((2, 1), np.float32)}
        poisoned = mutate_batch("inf_loss", batch, seed=0)
        with np.errstate(over="ignore"):
            assert np.square(poisoned["target"]).max() == np.inf
        banked.add("inf_loss")
        missing = set(KINDS) - banked
        assert not missing, f"chaos kinds with no drill coverage: {missing}"
