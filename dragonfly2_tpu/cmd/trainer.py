"""``df2-trainer`` — run the trainer service (real TPU training).

Reference counterpart: cmd/trainer + trainer/trainer.go — except the
training jobs are implemented (the reference's are TODO stubs).
"""

from __future__ import annotations

import argparse
import sys

from dragonfly2_tpu.cmd.common import (
    init_tracing,
    parse_with_config,
    add_common_flags,
    add_multihost_flags,
    init_logging,
    maybe_init_multihost,
    start_debug_monitor,
    start_metrics_server,
    wait_for_shutdown,
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("df2-trainer")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=9090)
    parser.add_argument("--data-dir", default="./trainer-data")
    parser.add_argument("--manager-db", default="",
                        help="manager sqlite path for model registration "
                             "(co-located deployment)")
    parser.add_argument("--object-store-dir", default="./manager-objects")
    parser.add_argument("--train-gat", action="store_true",
                        help="also train + register the GraphTransformer "
                             "(BASELINE config #3) each cycle")
    parser.add_argument("--train-seq", default="", metavar="CONFIG_JSON",
                        help="also train + register a sequence model "
                             "from the host's token segments each cycle; "
                             "the file holds the model's published "
                             "config.json keys, its model_type naming "
                             "the family (lfm2_moe, laguna or KeyeVL2), and may "
                             "add what is held here and the job's "
                             "settings (train/seq_trainer.py "
                             "config_from_dict)")
    parser.add_argument("--train-interval", type=float, default=0.0,
                        help="seconds between periodic retrain cycles: "
                             "every interval, hosts with NEW closed "
                             "dataset segments are retrained + "
                             "registered without waiting for the next "
                             "announcer stream EOF (0 = off; cycles and "
                             "skips counted in TrainerMetrics)")
    parser.add_argument("--profile-dir", default="",
                        help="run every model job under "
                             "jax.profiler.trace; XPlane dumps land in "
                             "<dir>/<model>/ with the loops' df2.train.* "
                             "spans and df2.* scopes (read with "
                             "df2-trace-tool train, or tensorboard/xprof)")
    parser.add_argument("--federated-quorum", type=int, default=0,
                        help="K-of-N quorum for federated rounds driven "
                             "from the training cycle (0 = federation "
                             "off). Endpoints come from this trainer's "
                             "replay segments grouped by scheduler id; "
                             "each cycle commits one screened round "
                             "through the journal in "
                             "<data-dir>/federation")
    parser.add_argument("--round-deadline", type=float, default=60.0,
                        help="federated straggler deadline per round, "
                             "seconds: a slow or dead cluster delays "
                             "nothing past it")
    parser.add_argument("--aggregator", default="fedavg",
                        choices=("fedavg", "trimmed_mean"),
                        help="federated aggregator (trimmed_mean is the "
                             "Byzantine-robust coordinate-wise trim)")
    add_multihost_flags(parser)
    add_common_flags(parser)
    args = parse_with_config(parser, argv)
    init_logging(args.verbose, args.log_dir, service="trainer")
    init_tracing(args, "trainer")
    # Before the first compile: every restart otherwise recompiles every
    # train step (configuration only — no backend is touched here).
    from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    # Joining a fleet must precede any other JAX use in the process.
    fleet_mesh = maybe_init_multihost(args)

    from dragonfly2_tpu import __version__
    from dragonfly2_tpu.rpc import serve
    from dragonfly2_tpu.trainer import (
        TRAINER_SPEC,
        TrainerService,
        TrainerStorage,
        Training,
    )
    from dragonfly2_tpu.trainer.metrics import TrainerMetrics

    registry = None
    if args.manager_db:
        from dragonfly2_tpu.manager import (
            Database,
            FilesystemObjectStore,
            ManagerService,
        )

        registry = ManagerService(
            Database(args.manager_db),
            FilesystemObjectStore(args.object_store_dir))
    storage = TrainerStorage(args.data_dir)
    metrics = TrainerMetrics(version=__version__)
    training_config = None
    if args.profile_dir or args.train_gat or args.train_seq:
        from dragonfly2_tpu.trainer.training import TrainingConfig

        training_config = TrainingConfig(train_gat_model=args.train_gat,
                                         profile_dir=args.profile_dir)
        if args.train_seq:
            import json

            from dragonfly2_tpu.train.seq_trainer import config_from_dict

            with open(args.train_seq, encoding="utf-8") as fh:
                training_config.seq = config_from_dict(json.load(fh))
            training_config.train_seq_model = True
    service = TrainerService(
        storage,
        Training(storage, registry, config=training_config,
                 metrics=metrics, mesh=fleet_mesh),
        metrics=metrics)
    server = serve([(TRAINER_SPEC, service)], host=args.host, port=args.port)
    print(f"trainer serving on {server.target}", flush=True)
    if args.federated_quorum > 0:
        import os

        from dragonfly2_tpu.trainer.federation import (
            FederationConfig,
            FederationCoordinator,
            endpoints_from_storage,
        )
        from dragonfly2_tpu.train.federated import FederatedConfig

        fed_config = FederationConfig(
            fed=FederatedConfig(aggregator=args.aggregator),
            quorum=args.federated_quorum,
            round_deadline_s=args.round_deadline)

        # Endpoints follow the streamed datasets: (re)build from replay
        # segments at each cycle so clusters that announce later join
        # the next round.
        class _LazyFederation:
            def __init__(self):
                self._coordinator = None

            def run_round(self):
                endpoints = endpoints_from_storage(
                    storage, service._host_identities,
                    fed_config.fed.local)
                if len(endpoints) < args.federated_quorum:
                    raise RuntimeError(
                        f"{len(endpoints)} federated endpoints < quorum "
                        f"{args.federated_quorum}; waiting for replay "
                        f"segments")
                self._coordinator = FederationCoordinator(
                    endpoints,
                    os.path.join(args.data_dir, "federation"),
                    fed_config, manager=registry)
                return self._coordinator.run_round()

        service.attach_federation(_LazyFederation())
        print(f"federation enabled: quorum={args.federated_quorum} "
              f"deadline={args.round_deadline:g}s "
              f"aggregator={args.aggregator}", flush=True)
    if args.train_interval > 0:
        service.start_cycle_driver(args.train_interval)
        print(f"interval cycle driver running every "
              f"{args.train_interval:g}s", flush=True)
    metrics_server = start_metrics_server(args, metrics.registry)
    debug_monitor = start_debug_monitor(args)
    wait_for_shutdown()
    service.stop_cycle_driver()
    if metrics_server:
        metrics_server.stop()
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
