"""FL launcher: run the FedSpace protocol (or any registered scheduler)
over the satellite constellation — the paper's system as a deployable
driver, built entirely through the declarative `repro.fl.api` layer.

    PYTHONPATH=src python -m repro.launch.fl_train --scheduler fedspace \
        --setting noniid --days 10 --target-acc 0.4

Any scheduler registered via `@register_scheduler` is selectable by name;
`--metrics-jsonl` streams eval metrics live to a JSONL file.
"""
from __future__ import annotations

import argparse
import json

from repro import compile_cache
from repro.fl.api import (AdapterConfig, ConstellationConfig, DatasetConfig,
                          FLExperiment, Federation, PartitionConfig,
                          SchedulerConfig)
from repro.fl.callbacks import JsonlMetricsCallback, ProgressCallback
from repro.fl.engine import EngineConfig
from repro.fl.registry import ADAPTERS, SCHEDULERS


def build_experiment(args) -> FLExperiment:
    scheduler = SchedulerConfig(kind=args.scheduler)
    if args.scheduler == "fedbuff":
        scheduler.params["M"] = args.M
    if args.scheduler == "fedspace":
        scheduler.setup = {"local_steps": args.local_steps,
                           "client_lr": args.client_lr}
    return FLExperiment(
        name=f"fl_train-{args.scheduler}-{args.setting}",
        constellation=ConstellationConfig(
            num_satellites=args.satellites, days=min(args.days, 5.0)),
        dataset=DatasetConfig(num_train=args.num_train,
                              num_val=args.num_train // 5, noise=2.2),
        partition=PartitionConfig(kind=args.setting),
        adapter=AdapterConfig(
            kind=args.model,
            params={"hidden": 48} if args.model == "mlp" else {}),
        scheduler=scheduler,
        train=EngineConfig(local_steps=args.local_steps,
                           client_lr=args.client_lr, eval_every=24,
                           target_acc=args.target_acc,
                           max_windows=int(args.days * 96),
                           repeat_connectivity=0),   # auto-tile C
        seed=args.seed,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheduler", default="fedspace",
                    choices=SCHEDULERS.names())
    ap.add_argument("--setting", default="noniid",
                    choices=["iid", "noniid"])
    ap.add_argument("--model", default="mlp", choices=ADAPTERS.names())
    ap.add_argument("--satellites", type=int, default=191)
    ap.add_argument("--days", type=float, default=10.0)
    ap.add_argument("--target-acc", type=float, default=0.40)
    ap.add_argument("--client-lr", type=float, default=1.0)
    ap.add_argument("--local-steps", type=int, default=16)
    ap.add_argument("--num-train", type=int, default=9600)
    ap.add_argument("--M", type=int, default=96, help="FedBuff buffer")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-jsonl", default=None,
                    help="stream eval metrics to this JSONL file")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    compile_cache.enable()
    fed = Federation.from_experiment(build_experiment(args))
    if fed.scheduler_diag:
        print(f"utility regressor: {fed.scheduler_diag}")

    callbacks = [ProgressCallback()]
    if args.metrics_jsonl:
        callbacks.append(JsonlMetricsCallback(args.metrics_jsonl))
    res = fed.run(callbacks=callbacks)

    summary = res.summary()
    print(json.dumps(summary, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"summary": summary, "accuracy": res.accuracy,
                       "eval_windows": res.eval_windows}, f, indent=1)


if __name__ == "__main__":
    main()
