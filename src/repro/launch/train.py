"""FL training launcher.

Two modes:
  * ``simulate`` (default) — the paper's experiment: host-level FL over the
    synthetic federated datasets with FedTune, small models, CPU-friendly.
  * ``mesh`` — the datacenter path: run ``fl_train_step`` (the dry-run
    artifact) on whatever devices exist, reduced arch.

Usage:
  PYTHONPATH=src python -m repro.launch.train --dataset emnist \
      --preference 0.25,0.25,0.25,0.25 --rounds 100 [--fedtune]
  PYTHONPATH=src python -m repro.launch.train --runtime buffered \
      --het stragglers --buffer-k 8 --fedtune
  PYTHONPATH=src python -m repro.launch.train --mode mesh --arch gemma2-2b

``--runtime`` picks the execution mode of the event-driven runtime
(sync = deadline rounds, async = FedAsync staleness weighting, buffered =
FedBuff K-update aggregation); ``--het`` samples a device fleet from a
named heterogeneity profile (homogeneous | mild | stragglers | mobile);
``--client-exec`` picks the sync-mode client-execution backend
(sequential | batched | sharded — sharded lays the cohort over a
``clients`` mesh axis and needs >1 device, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import argparse

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("simulate", "mesh"), default="simulate")
    ap.add_argument("--dataset", default="emnist",
                    choices=("speech_command", "emnist", "cifar100"))
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--preference", default="0.25,0.25,0.25,0.25")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--target", type=float, default=0.5)
    ap.add_argument("--m", type=int, default=5)
    ap.add_argument("--e", type=float, default=2.0)
    ap.add_argument("--aggregator", default="fedavg")
    ap.add_argument("--fedtune", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--runtime", choices=("sync", "async", "buffered"),
                    default="sync")
    ap.add_argument("--het", default="homogeneous",
                    help="heterogeneity profile (homogeneous | mild | "
                         "stragglers | mobile)")
    ap.add_argument("--selection", default="random",
                    choices=("random", "guided", "smallest", "deadline"))
    ap.add_argument("--deadline-quantile", type=float, default=1.0,
                    help="sync: cut stragglers above this completion "
                         "quantile")
    ap.add_argument("--buffer-k", type=int, default=8,
                    help="buffered: updates aggregated per flush")
    ap.add_argument("--staleness-alpha", type=float, default=0.5)
    ap.add_argument("--batched", action="store_true",
                    help="deprecated alias for --client-exec batched")
    ap.add_argument("--client-exec", default=None,
                    choices=("sequential", "batched", "sharded"),
                    help="sync-mode client execution backend: sequential "
                         "per-client loop, batched vmapped cohort, or "
                         "sharded clients-as-mesh-axis (multi-device; on "
                         "CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--trace", nargs="?", const="runs/train.trace.json",
                    default=None, metavar="PATH",
                    help="record a dual-clock trace of the run: Chrome "
                         "trace-event JSON (open in Perfetto) plus a "
                         "metrics JSONL next to it; bit-parity-neutral")
    ap.add_argument("--trace-jax", action="store_true",
                    help="with --trace: also open jax.profiler trace "
                         "annotations per span")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.mode == "mesh":
        from examples import distributed_fl  # same path, shared driver
        import sys
        sys.argv = ["distributed_fl", "--arch", args.arch]
        distributed_fl.main()
        return

    from repro.configs.paper_models import MLPConfig
    from repro.core import CostModel, FedTune, FedTuneConfig, Preference
    from repro.core.tuner import HyperParams
    from repro.data import (cifar100_like, emnist_like, speech_command_like)
    from repro.federated import FLConfig, FLServer, get_aggregator
    from repro.models import build_model
    from repro.optim.optimizers import get_optimizer

    ds_fns = {"speech_command": speech_command_like, "emnist": emnist_like,
              "cifar100": cifar100_like}
    dataset = ds_fns[args.dataset](reduced=not args.full)
    in_dim = int(__import__("numpy").prod(dataset.spec.shape))
    model = build_model(MLPConfig(name="mlp", in_dim=in_dim, hidden=(48,),
                                  n_classes=dataset.spec.n_classes))
    n_params = sum(p.size for p in jax.tree.leaves(
        model.init(jax.random.PRNGKey(0))))

    a, b, g, d = (float(x) for x in args.preference.split(","))
    pref = Preference(a, b, g, d)
    tuner = (FedTune(FedTuneConfig(preference=pref),
                     HyperParams(args.m, args.e)) if args.fedtune else None)
    from repro.runtime import RuntimeConfig, sample_fleet
    fleet = (None if args.het == "homogeneous"
             else sample_fleet(args.het, dataset.n_clients, seed=0))
    rtcfg = RuntimeConfig(
        mode=args.runtime, deadline_quantile=args.deadline_quantile,
        buffer_k=args.buffer_k, staleness_alpha=args.staleness_alpha,
        client_exec=args.client_exec or
        ("batched" if args.batched else "sequential"))
    server = FLServer(
        model, dataset, get_aggregator(args.aggregator),
        get_optimizer("sgd", 0.03, momentum=0.9),
        CostModel(flops_per_example=2 * n_params, param_count=n_params),
        FLConfig(m=args.m, e=args.e, batch_size=10,
                 target_accuracy=args.target, max_rounds=args.rounds,
                 log_every=max(args.rounds // 20, 1),
                 selection=args.selection),
        tuner=tuner, fleet=fleet, runtime_config=rtcfg)
    if args.trace is not None:
        from repro import obs
        obs.enable(jax_annotations=args.trace_jax)
    res = server.run()
    if args.trace is not None:
        from repro import obs
        from repro.obs.export import (trace_paths_for, write_chrome_trace,
                                      write_metrics_jsonl)
        obs.disable()
        trace_path, metrics_path = trace_paths_for("", args.trace)
        write_chrome_trace(trace_path)
        write_metrics_jsonl(metrics_path)
        print(f"trace -> {trace_path}; metrics -> {metrics_path} — open "
              "the trace at https://ui.perfetto.dev", flush=True)
    c = res.total_cost
    print(f"\ndone: rounds={res.rounds} acc={res.final_accuracy:.3f} "
          f"M={res.final_m} E={res.final_e:g} t_sim={res.sim_time:.4g}")
    print(f"CompT={c.comp_t:.4g} TransT={c.trans_t:.4g} "
          f"CompL={c.comp_l:.4g} TransL={c.trans_l:.4g}")
    if args.checkpoint:
        from repro.checkpoint import save_checkpoint
        # final params come back in FLResult; checkpoint them with the
        # run's scalar summary as metadata
        save_checkpoint(args.checkpoint, res.params, step=res.rounds,
                        metadata={
                            "final_accuracy": res.final_accuracy,
                            "costs": list(c.as_tuple()),
                            "runtime": args.runtime,
                            "het": args.het,
                            "sim_time": res.sim_time,
                        })
        print(f"checkpoint written to {args.checkpoint}")


if __name__ == "__main__":
    main()
