"""Benchmark harness — one function per paper table/figure.

``python -m benchmarks.run [--full]`` executes every benchmark and prints a
``name,us_per_call,derived`` CSV line per benchmark (us_per_call = wall time
of the benchmark itself; derived = its headline result).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size runs (default: quick)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import (
        ablations,
        appendixA_preemption,
        fig1_embedding,
        fig2_iterative_mae,
        fig4_arrivals,
        fig6_batch_sizes,
        fig7_scalability,
        live_engine,
        multi_device,
        multi_node,
        predictor_calibration,
        prefill_preempt,
        rank_sched,
        scheduler_overhead,
        sim_scale,
        table2_predictor,
        table5_jct,
    )

    benches = [
        ("fig1_embedding", fig1_embedding.run,
         lambda rows: f"separation_ratio={rows[0]['separation_ratio']}"),
        ("fig4_arrivals", fig4_arrivals.run,
         lambda rows: f"gamma_fits_better={rows[0]['gamma_fits_better']};"
                      f"alpha={rows[0]['fit_alpha']}"),
        ("table2_predictor", table2_predictor.run,
         lambda rows: f"r2_untrained={rows[0]['r2']:.2f};"
                      f"r2_trained={rows[1]['r2']:.2f};"
                      f"mae_trained={rows[1]['mae']:.1f}"),
        ("fig2_iterative_mae", fig2_iterative_mae.run,
         lambda rows: "mae_by_step=" + "/".join(
             f"{r['mae']:.0f}" for r in rows)),
        ("scheduler_overhead", scheduler_overhead.run,
         lambda rows: "isrtf_one_dispatch_per_window=" + str(all(
             r["dispatches"] == r["windows"] for r in rows
             if r["policy"] == "isrtf" and r["repredict_every"] == 1))
         + ";max_traces=" + str(max(r.get("num_traces", 0) for r in rows))),
        ("table5_jct", table5_jct.run,
         lambda rows: f"mean_isrtf_gain_pct={sum(r['isrtf_vs_fcfs_pct'] for r in rows)/len(rows):.1f}"),
        ("predictor_calibration", predictor_calibration.run,
         lambda rows: "ema_bias=" + str(predictor_calibration.cell(
             rows, regime="biased_oracle", calibrate="ema",
             risk_quantile=None)["pred_bias"])
         + ";coverage_q0.9=" + str(rows[0].get("coverage_q0.9"))),
        ("rank_sched", rank_sched.run,
         lambda rows: f"tau_regression={rows[0]['tau_regression']};"
                      f"tau_rank={rows[0]['tau_rank']};"
                      f"rank_isrtf_jct={rank_sched.cell(rows, predictor='ranked', policy='isrtf', calibrate='none')['jct_mean']}"),
        ("multi_node", multi_node.run,
         lambda rows: "hetero_fcfs_lpw_gain_pct=" + "/".join(
             f"{100 * (1 - multi_node.cell(rows, cluster='hetero', ordering='fcfs', n_nodes=n, placement='least_predicted_work', rebalance=False)['jct_mean'] / multi_node.cell(rows, cluster='hetero', ordering='fcfs', n_nodes=n, placement='least_jobs', rebalance=False)['jct_mean']):.1f}"
             for n in sorted({r["n_nodes"] for r in rows}))),
        ("fig6_batch_sizes", fig6_batch_sizes.run,
         lambda rows: f"max_gain_pct={max(r['improvement_pct'] for r in rows):.1f}"),
        ("fig7_scalability", fig7_scalability.run,
         lambda rows: f"peak_rps@{rows[-2]['n_workers']}w={rows[-2]['peak_rps']}"),
        ("appendixA_preemption", appendixA_preemption.run,
         lambda rows: f"onset_within_2x={sum(1 for r in rows if r.get('within_2x_of_paper'))}/5"),
        ("live_engine", live_engine.run,
         lambda rows: "live_gain_pct=" + str(next(
             r["live_isrtf_vs_fcfs_improvement_pct"] for r in rows
             if "live_isrtf_vs_fcfs_improvement_pct" in r))
         + ";live_vs_sim_ratio=" + str(next(
             r["calibration"]["live_vs_sim_ratio"] for r in rows
             if "calibration" in r))),
        ("multi_device", multi_device.run,
         # tolerant: on a 1-device host the bench returns a skip note
         lambda rows: rows[0].get("note") or (
             "live_vs_sim_ratio=" + str(next(
                 r["sim_replay"]["live_vs_sim_ratio"] for r in rows
                 if "sim_replay" in r))
             + ";eta_jct_s=" + str(next(
                 r["jct_mean_s"] for r in rows
                 if r.get("placement") == "least_eta")))),
        ("prefill_preempt", prefill_preempt.run,
         lambda rows: "chunk_jct_ratio=" + str(min(
             r["jct_vs_unchunked"] for r in rows
             if r["regime"] == "mixed_prompts"
             and r["prefill_chunk"] is not None))
         + ";auto_vs_recompute=" + str(min(
             r["jct_vs_recompute"] for r in rows
             if r.get("preempt_policy") == "auto"))),
        ("sim_scale", sim_scale.run,
         lambda rows: f"requests_per_s={rows[0]['requests_per_s']};"
                      f"peak_rss_mb={rows[0]['peak_rss_mb']};"
                      f"trace_identical={rows[-1]['trace_identical']}"),
        ("ablations", ablations.run,
         lambda rows: "mlfq_gain_pct=" + str(next(
             (r["gain_vs_fcfs_pct"] for r in rows
              if r.get("ablation") == "mlfq_comparison"
              and r.get("policy") == "mlfq"), "?")) + ";sigma_sweep=" + "/".join(
             f"{r['gain_vs_fcfs_pct']:.0f}" for r in rows
             if r.get("ablation") == "predictor_quality" and "sigma_rel" in r)),
    ]

    print("name,us_per_call,derived")
    for name, fn, derive in benches:
        if args.only and args.only != name:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn(quick=quick)
            derived = derive(rows) if rows else "no-results"
        except Exception as e:  # noqa: BLE001
            derived = f"ERROR:{e!r}"
            rows = []
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}", flush=True)


if __name__ == "__main__":
    main()
