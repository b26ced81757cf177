"""CI acceptance floors over the latest BENCH_*.json run records.

One shared gate script (the per-step heredocs used to copy-paste the
record-scanning logic): each subcommand reads the newest bench run that
carries its key and asserts the machine-independent ratio floors — both
sides of every ratio are measured in the SAME bench run on the same
machine.

  python -m benchmarks.check_floors deploy      # §12 deployed fast path
  python -m benchmarks.check_floors prefill     # §13 chunked prefill
  python -m benchmarks.check_floors megakernel  # §15 fused decode step
  python -m benchmarks.check_floors overload    # §16 front-end soak
  python -m benchmarks.check_floors drift       # §17 drift + calibration
"""

from __future__ import annotations

import json
import sys


def last_with(path: str, key: str) -> dict:
    for run in reversed(json.load(open(path))):
        if key in run:
            return run
    raise SystemExit(f"{path}: no recorded run with {key}")


def _floor(name: str, value, op: str, floor) -> None:
    """Uniform floor gate: every check reports the same way, and a failure
    always names the floor and the measured value (the old bare asserts
    made CI logs a guessing game)."""
    ok = {">=": value >= floor, "<=": value <= floor}[op]
    status = "ok" if ok else "FAILED"
    print(f"floor {status}: {name} = {value:.4g} (must be {op} {floor:g})")
    if not ok:
        raise SystemExit(
            f"FLOOR FAILED: {name} = {value:.4g}, required {op} {floor:g}")


def check_deploy() -> None:
    """deploy_speedup_sim >= 1.15 (deployed vs per-call-quantization
    engine, same run); decode_cost_ratio >= 4 (modeled decode-tile cost of
    the bm=256 pad vs the skinny tile).

    Floor history: PR 4 set 1.2 against a recorded 1.82 — but that sample
    came from the *unpaired* differenced measurement, whose machine drift
    between the two engine timings spans 0.73-1.62x across identical runs.
    The paired-median measurement (PR 5, ``_deploy_ratio_samples``) puts
    the true ratio at ~1.2-1.3 on the same container *including on the
    unchanged PR 4 code*, so 1.2 had zero margin; 1.15 still cleanly
    separates a working fast path (~1.25) from a lost one (~1.0).
    """
    serving = last_with("BENCH_serving.json", "deploy_speedup_sim")
    kernels = last_with("BENCH_kernels.json", "decode_cost_ratio")
    dep = serving["deploy_speedup_sim"]
    cost = kernels["decode_cost_ratio"]
    print(f"deploy_speedup_sim = {dep:.2f}x (floor 1.15x; samples "
          f"{serving.get('deploy_speedup_sim_samples')})")
    print(f"sim_vs_pr3_x       = {serving['sim_vs_pr3_x']:.2f}x "
          "(>= 2x on the reference container)")
    _floor("deploy_speedup_sim", dep, ">=", 1.15)
    _floor("decode_cost_ratio", cost, ">=", 4.0)


def check_prefill() -> None:
    """Chunked prefill must beat whole-prompt buckets >= 1.5x on cold TTFT
    (mean or worst-request; 1 compiled chunk trace vs one per bucket) or
    warm mixed prefill/decode throughput, compiled einsum path wall-clock
    — and must compile exactly one prefill trace."""
    run = last_with("BENCH_serving.json", "accept_speedup_x")
    x = run["accept_speedup_x"]
    traces = run["chunked_prefill_traces_off"]
    print(f"chunked cold_ttft_x_off     = {run['cold_ttft_x_off']:.2f}x")
    if "cold_ttft_max_x_off" in run:
        print(f"chunked cold_ttft_max_x_off = "
              f"{run['cold_ttft_max_x_off']:.2f}x")
    print(f"chunked mixed_tok_s_x_off   = {run['mixed_tok_s_x_off']:.2f}x")
    print(f"accept metric: {run['accept_metric']}")
    print(f"prefill traces: chunked={traces} "
          f"whole={run['whole_prefill_traces_off']}")
    if traces != 1:
        raise SystemExit(
            f"FLOOR FAILED: chunked_prefill_traces_off = {traces}, "
            "required exactly 1 compiled trace")
    print(f"floor ok: chunked_prefill_traces_off = {traces}")
    _floor("accept_speedup_x", x, ">=", 1.5)


def check_faults() -> None:
    """§14 fault campaign: the guard must be quiet on a healthy macro
    (zero-fault false trips <= 1% of row positions), detect the bench fault
    scenario (recall >= 0.9 over trials), hold guarded ViT accuracy within
    1 pt of fault-free at the bench rate, and recover the end-to-end
    serving victim onto the digital path token for token."""
    run = last_with("BENCH_faults.json", "detection_recall")
    sweep = run.get("vit_fault_sweep", [])
    if sweep:
        rows = ", ".join(
            f"rate={e['adc_stuck_rate']:g}: unguarded "
            f"{e['unguarded_acc']:.3f} / guarded {e['guarded_acc']:.3f}"
            for e in sweep)
        print(f"vit sweep (clean {run['vit_clean_acc']:.3f}): {rows}")
    print(f"unguarded_drop_pt = {run['unguarded_drop_pt']:.2f} "
          "(context, ungated)")
    _floor("zero_fault_false_trip_rate",
           run["zero_fault_false_trip_rate"], "<=", 0.01)
    _floor("detection_recall", run["detection_recall"], ">=", 0.9)
    # bitcell-only sweep: the dense end is gated, the dilute rates are
    # recorded ungated with the physical reason carried in the record
    gate = run.get("cell_only_gate")
    if gate is not None:
        sweep = run["cell_only_detection_by_rate"]
        print(f"cell-only sweep: {sweep} "
              f"(ungated rates {gate['ungated_rates']}: {gate['reason']})")
        _floor(f"cell_only_recall@{gate['dense_rate']}",
               sweep[gate["dense_rate"]], ">=", gate["dense_min_recall"])
    # segmented ABFT (PR 10): the 0.05 dilute rate graduates to the gated
    # set — per-segment sums face a sqrt(G)-lower noise floor — and
    # segmentation must not buy detection with false trips
    sgate = run.get("segmented_cell_gate")
    if sgate is not None:
        sweep = run["segmented_cell_detection_by_rate"]
        print(f"segmented (G={run['segments']}) sweep: {sweep} "
              f"(still ungated {sgate['ungated_rates']}: {sgate['reason']})")
        _floor(f"segmented_recall@{sgate['gated_rate']}",
               sweep[sgate["gated_rate"]], ">=", sgate["min_recall"])
        _floor("segmented_zero_fault_false_trip_rate",
               run["segmented_zero_fault_false_trip_rate"], "<=", 0.01)
    _floor("guarded_drop_pt", run["guarded_drop_pt"], "<=", 1.0)
    _floor("victim_token_match_vs_digital",
           run["victim_token_match_vs_digital"], ">=", 1.0)
    _floor("slots_bitexact_vs_pinned_twin",
           float(run["slots_bitexact_vs_pinned_twin"]), ">=", 1.0)


def check_megakernel() -> None:
    """§15 megakernel decode step + single-launch scheduler:

    * ``launch_drop_x`` >= 2 — jitted launches per scheduler iteration
      must drop at least 2x vs the per-call path (serving_bench witness;
      the structural number interpret-mode wall-clock can't fake).
    * ``mixed_device_work_x_{off,sim}`` >= 0.95 — on the warm mixed
      workload the chunked fused-step engine must spend no more DEVICE
      seconds than the whole-prompt baseline, within measurement noise
      (prefill_bench, every launch timed under block_until_ready, paired
      reps + median). Medians measure ~1.03-1.17 off / ~0.98-1.05 sim
      with +-7% rep spread; a fused step that lost its decode fusion
      (masked decode forward every prefill iteration) reads ~0.85, so
      0.95 separates working from lost without flaking.
    * ``mixed_tok_s_x_{off,sim}`` >= 0.85 — wall-clock backstop for the
      regression class this PR fixed (0.81x sim at PR 5/6). Wall-clock
      PARITY is not gateable on this container: both engines pay ~0.7 ms
      per scheduler iteration of host dispatch that 2 cores cannot hide,
      which pins the honest paired-median ratio at parity within noise
      (0.94-1.04 measured).
    * MLA + ssm decode kernels vs their pure-jnp oracles, run inline on
      CPU interpret — the parity the new attn_impl='kernel' routes rest
      on, re-asserted at gate time rather than trusted from the test run.
    """
    serving = last_with("BENCH_serving.json", "launch_drop_x")
    prefill = last_with("BENCH_serving.json", "mixed_tok_s_x_off")
    print(f"launches/iter: fused={serving['launches_per_iter_fused']:.2f} "
          f"percall={serving['launches_per_iter_percall']:.2f}")
    _floor("launch_drop_x", serving["launch_drop_x"], ">=", 2.0)
    for mode in ("off", "sim"):
        print(f"mixed wall samples {mode}: "
              f"{prefill.get(f'mixed_tok_s_x_samples_{mode}')}")
        _floor(f"mixed_device_work_x_{mode}",
               prefill[f"mixed_device_work_x_{mode}"], ">=", 0.95)
        _floor(f"mixed_tok_s_x_{mode}",
               prefill[f"mixed_tok_s_x_{mode}"], ">=", 0.85)

    import jax
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels.mla_decode import mla_decode_attention
    from repro.kernels.ssm_scan import ssm_decode_step

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    b, h, lat, rhd, t = 2, 4, 16, 8, 24
    args = (jax.random.normal(ks[0], (b, h, lat)),
            jax.random.normal(ks[1], (b, h, rhd)),
            jax.random.normal(ks[2], (b, t, lat)),
            jax.random.normal(ks[3], (b, rhd, t)),
            jnp.array([24, 7], jnp.int32), 1.0 / (lat + rhd) ** 0.5)
    mla_err = float(jnp.max(jnp.abs(
        mla_decode_attention(*args, block_k=8)
        - kref.mla_decode_attention_ref(*args))))
    _floor("mla_kernel_parity_err", mla_err, "<=", 1e-4)

    di, ng, ds, nh, win = 64, 1, 16, 2, 3
    cd = di + 2 * ng * ds
    sargs = (jax.random.normal(ks[4], (b, win, cd)),
             jax.random.normal(ks[5], (b, 1, cd)),
             jax.random.normal(ks[6], (win + 1, cd)),
             jnp.zeros((cd,)),
             jax.nn.softplus(jax.random.normal(ks[7], (b, nh))),
             -jnp.ones((nh,)), jnp.ones((nh,)),
             jnp.zeros((b, nh, di // nh, ds)), di, ng, ds)
    got = ssm_decode_step(*sargs)
    want = kref.ssm_decode_step_ref(*sargs)
    ssm_err = max(float(jnp.max(jnp.abs(g - w)))
                  for g, w in zip(got, want))
    _floor("ssm_kernel_parity_err", ssm_err, "<=", 1e-4)


def check_overload() -> None:
    """§16 overload soak: the front-end must never lose or wedge a request
    (every submission ends in exactly one terminal outcome), bound the p99
    queue wait by the watermark policy (<= queue_limit services ahead of
    any admitted request, both sides measured in the same run), replay a
    retried request bit-for-bit under its stable rid, and restore full CB
    votes once the backlog drains below the low watermark."""
    run = last_with("BENCH_overload.json", "lost_requests")
    print(f"overload soak: {run['n_requests']} requests, "
          f"outcomes {run['outcomes']}")
    print(f"queue_wait p50/p99 = {run['queue_wait_p50_s']:.3f}s / "
          f"{run['queue_wait_p99_s']:.3f}s "
          f"(service_p99 {run['service_p99_s']:.3f}s)")
    print(f"ladder: {run['degraded_admissions']} degraded admissions, "
          f"{run['ladder_transitions']} transitions, recovery votes "
          f"{run['recovery_votes']}/{run['full_votes']}")
    _floor("lost_requests", run["lost_requests"], "<=", 0)
    _floor("wedged_requests", run["wedged_requests"], "<=", 0)
    # the soak sheds by design (waves of 10 into a 6-deep queue); a soak
    # that shed nothing never reached overload and proves nothing
    _floor("shed_fraction", run["shed_fraction"], ">=", 0.01)
    _floor("queue_wait_p99_x", run["queue_wait_p99_x"], "<=", 1.0)
    _floor("retry_bit_identical", run["retry_bit_identical"], ">=", 1.0)
    _floor("vote_recovery", run["vote_recovery"], ">=", 1.0)
    _floor("degraded_admissions", run["degraded_admissions"], ">=", 1)


def check_drift() -> None:
    """§17 drift soak: the injected trajectory must actually hurt (an
    uncalibrated ViT twin drops >= 5 pt — a cosmetic drift proves nothing),
    online calibration must recover it (within 1 pt of drift-free on the
    SAME trajectory, and the SQNR soak back within a couple dB of the
    drift-free plane), the canary watchdog must flag the injected abrupt
    supply step inside its analytic detection bound, and an all-zero
    DriftSpec engine must stay bit-identical to a drift-free engine."""
    run = last_with("BENCH_drift.json", "vit_drop_uncal_pt")
    print(f"vit acc: free {run['vit_acc_driftfree']:.3f} / uncal "
          f"{run['vit_acc_uncalibrated']:.3f} / cal "
          f"{run['vit_acc_calibrated']:.3f} (step {run['vit_soak_step']}, "
          f"calib quality {run['vit_calib_quality']:.2f})")
    print(f"sqnr: free {run['sqnr_free_db']:.1f} dB, worst uncal gap "
          f"{run['sqnr_uncal_gap_db']:.1f} dB, worst cal gap "
          f"{run['sqnr_cal_gap_db']:.1f} dB")
    print(f"watchdog: event step {run['watchdog_event_step']}, trip step "
          f"{run['watchdog_trip_step']} (bound "
          f"{run['watchdog_latency_bound']})")
    _floor("vit_drop_uncal_pt", run["vit_drop_uncal_pt"], ">=", 5.0)
    _floor("vit_drop_cal_pt", run["vit_drop_cal_pt"], "<=", 1.0)
    _floor("sqnr_uncal_gap_db", run["sqnr_uncal_gap_db"], ">=", 10.0)
    _floor("sqnr_cal_gap_db", run["sqnr_cal_gap_db"], "<=", 3.0)
    _floor("watchdog_latency_steps", run["watchdog_latency_steps"],
           "<=", run["watchdog_latency_bound"])
    _floor("zero_drift_token_match", run["zero_drift_token_match"],
           ">=", 1.0)


def check_scaleout() -> None:
    """§18 scale-out: TP dryrun plans must resolve for both target configs,
    the live sharded deploy must be placement-only (bit-identical planes),
    modeled replica scaling >= 0.7x linear at N=4 (busy-time model — the
    CI host is one core, so parallel wall clock is unobservable; the
    serial wall ratio is printed as ungated context), and the failover
    soak must lose nothing: every stream terminal, none silently short,
    every kill/wedge-migrated stream bit-identical to its unkilled twin."""
    run = last_with("BENCH_scaleout.json", "scaling_x_n4")
    for name, plan in run["dryrun"].items():
        print(f"dryrun {name}: planes {plan['weight_planes']} "
              f"(tp {plan['tp_sharded_planes']}), "
              f"{plan['int8_gib_total']} GiB -> "
              f"{plan['int8_gib_per_device']} GiB/device")
        _floor(f"dryrun_ok[{name}]", float(plan["ok"]), ">=", 1.0)
        _floor(f"tp_sharded_planes[{name}]",
               plan["tp_sharded_planes"], ">=", 1)
    _floor("shard_bit_identical", run["shard_bit_identical"], ">=", 1.0)
    _floor("shard_multi_device_planes",
           run["shard_multi_device_planes"], ">=", 1)
    print(f"serial_wall_ratio_n4 = {run['serial_wall_ratio_n4']} "
          "(context, ungated: one-core host)")
    _floor("scaling_x_n4", run["scaling_x_n4"], ">=", 2.8)
    _floor("soak_lost", run["soak_lost"], "<=", 0)
    _floor("soak_wedged_streams", run["soak_wedged_streams"], "<=", 0)
    _floor("soak_migrated", run["soak_migrated"], ">=", 1)
    _floor("migrated_bit_identical",
           run["migrated_bit_identical"], ">=", 1.0)
    _floor("storm_victim_drained", run["storm_victim_drained"], ">=", 1.0)


CHECKS = {"deploy": check_deploy, "prefill": check_prefill,
          "faults": check_faults, "megakernel": check_megakernel,
          "overload": check_overload, "drift": check_drift,
          "scaleout": check_scaleout}


def main(argv) -> None:
    if len(argv) != 1 or argv[0] not in CHECKS:
        raise SystemExit(f"usage: check_floors {{{'|'.join(CHECKS)}}}")
    CHECKS[argv[0]]()


if __name__ == "__main__":
    main(sys.argv[1:])
