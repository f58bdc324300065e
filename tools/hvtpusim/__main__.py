"""CLI for the fabric simulator (see package docstring)."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys


def _ensure_deterministic_interpreter() -> None:
    """Re-exec once with PYTHONHASHSEED=0 so any hash-order-dependent
    iteration inside the interpreter is identical across runs — the
    byte-identical event-log contract must not hinge on hash
    randomisation."""
    if os.environ.get("PYTHONHASHSEED") == "0":
        return
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = "0"
    os.execve(sys.executable,
              [sys.executable, "-m", "tools.hvtpusim"] + sys.argv[1:],
              env)


def _parse_kv(pairs):
    """--set key=value scenario kwargs (ints/floats/bools parsed)."""
    out = {}
    for p in pairs or ():
        if "=" not in p:
            raise SystemExit(f"--set expects key=value, got {p!r}")
        k, v = p.split("=", 1)
        k = k.strip().replace("-", "_")
        v = v.strip()
        if v.lower() in ("true", "false"):
            out[k] = v.lower() == "true"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out


def _dump(result, out_path):
    lines = "".join(
        json.dumps(rec, sort_keys=True) + "\n" for rec in result["events"])
    digest = hashlib.sha256(lines.encode()).hexdigest()
    if out_path:
        with open(out_path, "w") as f:
            f.write(lines)
    return digest, len(result["events"])


def _cmd_list(_args) -> int:
    from horovod_tpu.sim.scenarios import SCENARIOS

    width = max(len(n) for n in SCENARIOS)
    for name, fn in sorted(SCENARIOS.items()):
        doc = (fn.__doc__ or "").strip().split("\n")[0]
        print(f"{name:<{width}}  {doc}")
    return 0


def _cmd_run(args) -> int:
    from horovod_tpu.sim.scenarios import run_scenario

    kwargs = _parse_kv(args.set)
    result = run_scenario(args.scenario, args.ranks, args.seed, **kwargs)
    digest, n_events = _dump(result, args.out)
    report = {
        "scenario": result["scenario"],
        "ranks": result["ranks"],
        "seed": result["seed"],
        "stats": result["stats"],
        "events": n_events,
        "event_log_sha256": digest,
    }
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0


#: World sizes for the measured control-plane rows.  1024 is the
#: acceptance scale; 4096 works but is a coffee break, so it stays
#: opt-in via --ranks.
_BENCH_RANKS = (64, 256, 1024)


def bench_rows(ranks_list, seed: int = 0):
    """Measured control-plane timings vs world size: negotiation cycle
    (lockstep KVTransport exchange), rendezvous (audit digest
    allgather), and drain commit (notice → agreed durable commit).
    Virtual time on the default healthy-link model (50us latency,
    1 GbE, 10% jitter)."""
    from horovod_tpu.sim.scenarios import (bench_negotiation,
                                           steady_drain,
                                           thundering_rendezvous)

    rows = []
    for ranks in ranks_list:
        neg = bench_negotiation(ranks, seed)["stats"]["phases"]["negotiate"]
        rdv = thundering_rendezvous(ranks, seed)["stats"]["phases"][
            "rendezvous"]
        drn = steady_drain(ranks, seed)["stats"]["phases"]["drain"]
        rows.append({
            "ranks": ranks,
            "negotiation_cycle_p50_s": neg["cycle_p50_s"],
            "negotiation_cycle_max_s": neg["cycle_max_s"],
            "rendezvous_s": round(rdv["virtual_s"], 6),
            "rendezvous_p50_s": round(rdv["p50_s"], 6),
            "drain_notice_to_commit_s": drn["notice_to_commit_s"],
            "measured": True,
            "method": "fabric-sim virtual time, seed %d" % seed,
        })
        print(f"ranks={ranks}: negotiation p50 "
              f"{neg['cycle_p50_s'] * 1000:.2f} ms, rendezvous "
              f"{rdv['virtual_s']:.3f} s, drain notice→commit "
              f"{drn['notice_to_commit_s']:.3f} s", file=sys.stderr)
    return rows


def fleet_bench_rows(ranks_list, seed: int = 0):
    """Measured multi-job arbiter timings vs pool size: queue wait for
    a gang-scheduled high-priority arrival, preemption notice → agreed
    durable commit on the victim, and the victim's full resize latency
    (drain + relaunch at the smaller world).  Virtual time on the
    default healthy-link model."""
    import logging

    from horovod_tpu.sim.scenarios import multi_job_arbiter

    # every simulated rank shares this process's logger, so the
    # per-peer notice warning is O(ranks * victims) lines at 1024+ —
    # half a million for a bench that reports five numbers
    hvt_logger = logging.getLogger("horovod_tpu")
    prior_level = hvt_logger.level
    hvt_logger.setLevel(logging.ERROR)
    try:
        return _fleet_bench_rows(ranks_list, seed)
    finally:
        hvt_logger.setLevel(prior_level)


def _fleet_bench_rows(ranks_list, seed):
    from horovod_tpu.sim.scenarios import multi_job_arbiter

    rows = []
    for ranks in ranks_list:
        ph = multi_job_arbiter(ranks, seed)["stats"]["phases"]
        pre = ph["preempt"]
        rows.append({
            "ranks": ranks,
            "queue_wait_s": round(pre["queue_wait_s"], 6),
            "preempt_notice_to_commit_s": round(
                pre["notice_to_commit_s"], 6),
            "resize_s": round(pre["resize_s"], 6),
            "victims": pre["victims"],
            "measured": True,
            "method": "fabric-sim virtual time, seed %d" % seed,
        })
        print(f"ranks={ranks}: queue wait {pre['queue_wait_s']:.3f} s, "
              f"preempt notice→commit {pre['notice_to_commit_s']:.3f} s, "
              f"resize {pre['resize_s']:.3f} s "
              f"({pre['victims']} victims)", file=sys.stderr)
    return rows


def _cmd_bench_fleet(args) -> int:
    ranks_list = [int(r) for r in args.ranks.split(",") if r.strip()]
    rows = fleet_bench_rows(ranks_list, seed=args.seed)
    print(json.dumps({"fleet_arbiter_sim": rows}, indent=1,
                     sort_keys=True))
    if args.update:
        path = args.update
        with open(path) as f:
            doc = json.load(f)
        doc["fleet_arbiter_sim"] = {
            "note": (
                "MEASURED on the fabric simulator: the real FleetArbiter "
                "(horovod_tpu/fleet) arbitrating two jobs over one "
                "virtual pool — a high-priority gang arrival preempts "
                "half the low-priority world through the graceful-drain "
                "channel (exit 79, zero budget strikes).  queue_wait_s "
                "is submit → gang placement for the arrival; "
                "preempt_notice_to_commit_s is drain notice → agreed "
                "durable commit on the victim; resize_s is notice → "
                "relaunch at the smaller world."),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"updated {path}", file=sys.stderr)
    return 0


def ckpt_bench_rows(ranks_list, seed: int = 0):
    """Measured durable-state-plane timings vs world size: snapshot
    commit latency (modeled disk + the real commit protocol) and the
    restore-quorum agreement time under injected torn/bitflip damage
    (checkpoint-storm scenario).  Virtual time on the default
    healthy-link model."""
    import logging

    # the two storage-damage victims log warnings through the shared
    # process logger; silence them for a bench that reports numbers
    hvt_logger = logging.getLogger("horovod_tpu")
    prior_level = hvt_logger.level
    hvt_logger.setLevel(logging.ERROR)
    try:
        return _ckpt_bench_rows(ranks_list, seed)
    finally:
        hvt_logger.setLevel(prior_level)


def _ckpt_bench_rows(ranks_list, seed):
    from horovod_tpu.sim.scenarios import checkpoint_storm

    rows = []
    for ranks in ranks_list:
        ph = checkpoint_storm(ranks, seed)["stats"]["phases"]
        cm, rq = ph["commit"], ph["restore_quorum"]
        rows.append({
            "ranks": ranks,
            "commit_p50_s": cm["commit_p50_s"],
            "commit_p99_s": cm["commit_p99_s"],
            "quorum_p50_s": rq["quorum_p50_s"],
            "quorum_max_s": rq["quorum_max_s"],
            "agreed_seq": rq["agreed_seq"],
            "measured": True,
            "method": "fabric-sim virtual time, seed %d" % seed,
        })
        print(f"ranks={ranks}: commit p50 "
              f"{cm['commit_p50_s'] * 1000:.2f} ms, restore quorum p50 "
              f"{rq['quorum_p50_s'] * 1000:.2f} ms / max "
              f"{rq['quorum_max_s'] * 1000:.2f} ms", file=sys.stderr)
    return rows


def _cmd_bench_ckpt(args) -> int:
    ranks_list = [int(r) for r in args.ranks.split(",") if r.strip()]
    rows = ckpt_bench_rows(ranks_list, seed=args.seed)
    print(json.dumps({"checkpoint_storm_sim": rows}, indent=1,
                     sort_keys=True))
    if args.update:
        path = args.update
        with open(path) as f:
            doc = json.load(f)
        doc["checkpoint_storm_sim"] = {
            "note": (
                "MEASURED on the fabric simulator: the real durable "
                "commit protocol (horovod_tpu/core/durable.py) at "
                "virtual scale with injected ckpt.write torn/bitflip "
                "damage on two victims' final commit.  commit_*_s is "
                "one snapshot commit (modeled disk at 200 MB/s + 2 ms "
                "base, payload writes + manifest rename); quorum_*_s "
                "is one rank's restore-quorum round (publish highest "
                "verified seq, blocking-read all peers, agree on the "
                "min).  The damaged commits lower the agreed seq by "
                "one — never diverge it."),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"updated {path}", file=sys.stderr)
    return 0


def anomaly_bench_rows(ranks_list, seed: int = 0, seeds: int = 5):
    """Measured straggler-detection latency vs world size: virtual
    seconds from a mid-run ``set_link`` degradation of one rank to the
    first straggler incident naming exactly that rank
    (anomaly-detection scenario, real AnomalyEngine).  p50/max over
    ``seeds`` independent seeds per world size."""
    from horovod_tpu.sim.scenarios import anomaly_detection

    rows = []
    for ranks in ranks_list:
        lats = []
        for s in range(seed, seed + seeds):
            ph = anomaly_detection(ranks, s)["stats"]["phases"]["detect"]
            lats.append(ph["detection_latency_s"])
        lats.sort()
        rows.append({
            "ranks": ranks,
            "detection_latency_p50_s": round(
                lats[len(lats) // 2], 6),
            "detection_latency_max_s": round(lats[-1], 6),
            "seeds": seeds,
            "measured": True,
            "method": "fabric-sim virtual time, seeds %d..%d" % (
                seed, seed + seeds - 1),
        })
        print(f"ranks={ranks}: detection latency p50 "
              f"{lats[len(lats) // 2]:.3f} s, max {lats[-1]:.3f} s "
              f"({seeds} seeds)", file=sys.stderr)
    return rows


def _cmd_bench_anomaly(args) -> int:
    ranks_list = [int(r) for r in args.ranks.split(",") if r.strip()]
    rows = anomaly_bench_rows(ranks_list, seed=args.seed)
    print(json.dumps({"anomaly_detection_sim": rows}, indent=1,
                     sort_keys=True))
    if args.update:
        path = args.update
        with open(path) as f:
            doc = json.load(f)
        doc["anomaly_detection_sim"] = {
            "note": (
                "MEASURED on the fabric simulator: the real "
                "AnomalyEngine (horovod_tpu/obs/anomaly.py) fed "
                "per-cycle arrival skew while one virtual rank's link "
                "degrades 400x mid-run via set_link.  "
                "detection_latency_*_s is virtual seconds from the "
                "degradation to the first straggler incident; the "
                "scenario asserts the incident names exactly the "
                "degraded rank."),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"updated {path}", file=sys.stderr)
    return 0


#: World sizes for the fleet-service front-door rows: the tier-1
#: storm plus the 4096/16384 scale proofs from the acceptance bar.
_SERVICE_BENCH_RANKS = (256, 4096, 16384)


def service_bench_rows(ranks_list, seed: int = 0):
    """Measured fleet front-door rows vs pool size: queue-wait
    percentiles by priority tier, submit→intake latency through the
    indexed journal, pool fragmentation, preemption churn, and the
    starvation guard's observed bound — all from the fleet-service
    storm scenario (which internally asserts exactly-once intake
    across an injected arbiter crash)."""
    from horovod_tpu.sim.scenarios import fleet_service

    rows = []
    for ranks in ranks_list:
        ph = fleet_service(ranks, seed)["stats"]["phases"]
        svc = ph["service"]
        rows.append({
            "ranks": ranks,
            "jobs": ph["pool"]["jobs"],
            "queue_wait_p50_s": svc["queue_wait_p50_s"],
            "queue_wait_p99_s": svc["queue_wait_p99_s"],
            "intake_p50_s": ph["intake"]["intake_p50_s"],
            "intake_p99_s": ph["intake"]["intake_p99_s"],
            "max_batch": ph["intake"]["max_batch"],
            "queue_full_rejections": ph["intake"][
                "queue_full_rejections"],
            "quota_rejections": ph["admission"]["rejected"],
            "replayed_duplicates": ph["crash"]["replayed_duplicates"],
            "frag_mean": ph["placement"]["frag_mean"],
            "preemptions": svc["preemptions"],
            "aged_jobs": svc["aged_jobs"],
            "starvation_gap_max_s": svc["aged_gap_max_s"],
            "measured": True,
            "method": "fabric-sim virtual time, seed %d" % seed,
        })
        print(f"ranks={ranks}: {ph['pool']['jobs']} jobs, "
              f"tier-0 wait p99 "
              f"{svc['queue_wait_p99_s']['0']:.1f} s, intake p99 "
              f"{ph['intake']['intake_p99_s']:.3f} s, frag "
              f"{ph['placement']['frag_mean']:.3f}, "
              f"{svc['preemptions']} preemptions", file=sys.stderr)
    return rows


def _cmd_bench_service(args) -> int:
    ranks_list = [int(r) for r in args.ranks.split(",") if r.strip()]
    rows = service_bench_rows(ranks_list, seed=args.seed)
    print(json.dumps({"fleet_service_sim": rows}, indent=1,
                     sort_keys=True))
    if args.update:
        path = args.update
        with open(path) as f:
            doc = json.load(f)
        doc["fleet_service_sim"] = {
            "note": (
                "MEASURED on the fabric simulator: the production "
                "front door end to end — a seeded multi-tenant "
                "submission storm through the REAL indexed journal "
                "(fleet/intake.py) into the REAL arbiter with "
                "tenants.json quotas, weighted fair share, the "
                "starvation guard, torus-aware placement, truthful "
                "queue-full backpressure, and an injected arbiter "
                "crash that rolls the intake cursor back mid-storm.  "
                "queue_wait_*_s keys by priority tier; intake_*_s is "
                "submit append -> arbiter intake; "
                "starvation_gap_max_s bounds aged-job wait past the "
                "aging threshold.  The scenario internally asserts "
                "exactly-once intake across the crash and a per-tick "
                "cost bounded by the intake budget."),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"updated {path}", file=sys.stderr)
    return 0


def lossy_bench_rows(ranks_list, seed: int = 7):
    """Measured wire-plane recovery rows vs world size: the lossy-link
    scenario with consensus abort-and-retry armed (zero restarts, zero
    torn collectives — asserted inside the scenario) against the SAME
    seed with retries disabled, where the first wire loss poisons the
    job and every later step is lost to the restart."""
    import logging

    # each consensus retry and reroute logs a warning through the
    # shared process logger; silence them for a bench that reports rows
    hvt_logger = logging.getLogger("horovod_tpu")
    prior_level = hvt_logger.level
    hvt_logger.setLevel(logging.ERROR)
    try:
        return _lossy_bench_rows(ranks_list, seed)
    finally:
        hvt_logger.setLevel(prior_level)


def _lossy_bench_rows(ranks_list, seed):
    from horovod_tpu.sim.scenarios import lossy_link

    rows = []
    for ranks in ranks_list:
        ll = lossy_link(ranks, seed)["stats"]["phases"]["lossy_link"]
        base = lossy_link(ranks, seed, baseline=True)[
            "stats"]["phases"]["lossy_link"]
        rows.append({
            "ranks": ranks,
            "steps": ll["steps"],
            "retry_rounds": ll["retry_rounds"],
            "recovered_collectives": ll["recovered_collectives"],
            "consensus_p50_s": ll["consensus_p50_s"],
            "consensus_max_s": ll["consensus_max_s"],
            "reroutes": ll["reroutes"],
            "torn": ll["torn"],
            "steps_lost_with_retries": ll["steps_lost"],
            "baseline_restarts": base["restarts"],
            "baseline_steps_lost": base["steps_lost"],
            "measured": True,
            "method": "fabric-sim virtual time, seed %d" % seed,
        })
        print(f"ranks={ranks}: {ll['recovered_collectives']} collectives "
              f"recovered over {ll['retry_rounds']} consensus rounds "
              f"(p50 {ll['consensus_p50_s'] * 1000:.1f} ms), "
              f"{ll['reroutes']} reroutes, {ll['torn']} torn; baseline "
              f"loses {base['steps_lost']}/{ll['steps']} steps to the "
              f"restart", file=sys.stderr)
    return rows


def _cmd_bench_lossy(args) -> int:
    ranks_list = [int(r) for r in args.ranks.split(",") if r.strip()]
    rows = lossy_bench_rows(ranks_list, seed=args.seed)
    print(json.dumps({"lossy_link_sim": rows}, indent=1,
                     sort_keys=True))
    if args.update:
        path = args.update
        with open(path) as f:
            doc = json.load(f)
        doc["lossy_link_sim"] = {
            "note": (
                "MEASURED on the fabric simulator: the wire plane "
                "under a lossy fabric — seeded per-edge drops, a "
                "mid-run link flap, and deterministic wire.send drop "
                "injections — recovered by the REAL consensus "
                "abort-and-retry protocol (comm/wirefault.py) over "
                "the fabric KV, with the REAL LinkHealth map rerouting "
                "the ring around the flapping rank.  The scenario "
                "asserts zero restarts and zero torn collectives "
                "(every retried delivery bitwise-equal to the clean "
                "run); consensus_*_s is vote post -> agreed decision.  "
                "baseline_* rows re-run the SAME seed with retries "
                "disabled: the first loss poisons the job and "
                "baseline_steps_lost of the run's steps are lost to "
                "the restart-the-world recovery."),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"updated {path}", file=sys.stderr)
    return 0


def _cmd_bench(args) -> int:
    ranks_list = [int(r) for r in args.ranks.split(",") if r.strip()]
    rows = bench_rows(ranks_list, seed=args.seed)
    print(json.dumps({"control_plane_sim": rows}, indent=1,
                     sort_keys=True))
    if args.update:
        path = args.update
        with open(path) as f:
            doc = json.load(f)
        doc["control_plane_sim"] = {
            "note": (
                "MEASURED on the fabric simulator (horovod_tpu/sim): "
                "real KVTransport/audit/drain code over the virtual-"
                "time KV with the default link model (50us, 1GbE, 10% "
                "jitter).  Protocol-faithful virtual-time "
                "measurements at the stated world sizes, not "
                "extrapolations, and not device time."),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"updated {path}", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    _ensure_deterministic_interpreter()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="hvtpusim",
        description="run the hvtpu control plane at virtual scale")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_run = sub.add_parser("run", help="run one named scenario")
    p_run.add_argument("scenario")
    p_run.add_argument("--ranks", type=int, default=256)
    p_run.add_argument("--seed", type=int, default=0)
    p_run.add_argument("--out", help="write the event log (JSONL) here")
    p_run.add_argument("--set", action="append", metavar="KEY=VAL",
                       help="scenario keyword override (repeatable)")
    p_run.set_defaults(fn=_cmd_run)
    p_list = sub.add_parser("list", help="list scenarios")
    p_list.set_defaults(fn=_cmd_list)
    p_bench = sub.add_parser(
        "bench", help="measured control-plane scaling rows")
    p_bench.add_argument(
        "--ranks", default=",".join(str(r) for r in _BENCH_RANKS))
    p_bench.add_argument("--seed", type=int, default=0)
    p_bench.add_argument(
        "--update", metavar="BENCH_SCALING.json",
        help="write the rows into this bench JSON")
    p_bench.set_defaults(fn=_cmd_bench)
    p_fleet = sub.add_parser(
        "bench-fleet", help="measured multi-job arbiter scaling rows")
    p_fleet.add_argument(
        "--ranks", default=",".join(str(r) for r in _BENCH_RANKS))
    p_fleet.add_argument("--seed", type=int, default=0)
    p_fleet.add_argument(
        "--update", metavar="BENCH_SCALING.json",
        help="write the rows into this bench JSON")
    p_fleet.set_defaults(fn=_cmd_bench_fleet)
    p_ckpt = sub.add_parser(
        "bench-ckpt", help="measured durable-state-plane scaling rows")
    p_ckpt.add_argument(
        "--ranks", default=",".join(str(r) for r in _BENCH_RANKS))
    p_ckpt.add_argument("--seed", type=int, default=0)
    p_ckpt.add_argument(
        "--update", metavar="BENCH_SCALING.json",
        help="write the rows into this bench JSON")
    p_ckpt.set_defaults(fn=_cmd_bench_ckpt)
    p_anom = sub.add_parser(
        "bench-anomaly",
        help="measured straggler-detection latency rows")
    p_anom.add_argument("--ranks", default="256,1024")
    p_anom.add_argument("--seed", type=int, default=0)
    p_anom.add_argument(
        "--update", metavar="BENCH_SCALING.json",
        help="write the rows into this bench JSON")
    p_anom.set_defaults(fn=_cmd_bench_anomaly)
    p_svc = sub.add_parser(
        "bench-service",
        help="measured fleet front-door (service) scaling rows")
    p_svc.add_argument(
        "--ranks",
        default=",".join(str(r) for r in _SERVICE_BENCH_RANKS))
    p_svc.add_argument("--seed", type=int, default=0)
    p_svc.add_argument(
        "--update", metavar="BENCH_SCALING.json",
        help="write the rows into this bench JSON")
    p_svc.set_defaults(fn=_cmd_bench_service)
    p_lossy = sub.add_parser(
        "bench-lossy",
        help="measured wire-plane recovery-vs-restart rows")
    p_lossy.add_argument(
        "--ranks", default=",".join(str(r) for r in _BENCH_RANKS))
    p_lossy.add_argument("--seed", type=int, default=7)
    p_lossy.add_argument(
        "--update", metavar="BENCH_SCALING.json",
        help="write the rows into this bench JSON")
    p_lossy.set_defaults(fn=_cmd_bench_lossy)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
