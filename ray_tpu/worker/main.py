"""Worker process entrypoint (reference:
python/ray/workers/default_worker.py): connect to the local raylet, register
into its pool, and run the task execution loop."""

from __future__ import annotations

import argparse
import logging
import os
import time


def main():
    entered = time.time()
    parser = argparse.ArgumentParser()
    parser.add_argument("--raylet-address", required=True)
    parser.add_argument("--gcs-address", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--store-root", required=True)
    parser.add_argument("--log-file", default=None)
    args = parser.parse_args()

    from ray_tpu._private import failpoints
    from ray_tpu._private.config import Config, get_config, set_config
    from ray_tpu._private.core_worker import WORKER, CoreWorker
    from ray_tpu._private.log_utils import setup_process_logging

    setup_process_logging("worker", args.log_file)
    failpoints.set_role("worker")
    set_config(Config.load())

    # JAX_PLATFORMS arrives set by the raylet (cpu for a CPU-flavour
    # worker, tpu for the chip-owning one); JAX's persistent compile
    # cache is placed here, before this process's first JAX use.
    from ray_tpu._private import compile_cache

    compile_cache.enable_persistent_cache()

    cw = CoreWorker(
        mode=WORKER,
        raylet_address=args.raylet_address,
        gcs_address=args.gcs_address,
        session_dir=args.session_dir,
        store_root=args.store_root,
        config=get_config(),
    )
    # print()/stderr from task code streams to the driver console
    # (reference: log_monitor.py:48 republishing).
    from ray_tpu._private.log_utils import install_stdout_forwarder

    install_stdout_forwarder(cw)
    if (os.environ.get("RAY_TPU_WORKER_FLAVOR") == "tpu"
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        # the chips this worker is leased may still be held by a worker
        # that is ending (another session's, or a killed one's of this
        # session): it waits for them, bounded, before the first user
        # code runs, since that is what initialises the backend
        from ray_tpu._private import accelerator

        nodes = accelerator.tpu_device_nodes()

        def chip_wait() -> dict:
            facts = {}
            accelerator.wait_for_chips(
                lambda: accelerator.held_nodes(nodes), facts=facts)
            return facts

        cw.before_user_code = chip_wait
    logging.getLogger("ray_tpu.worker").info(
        "worker %s registered with raylet %s",
        cw.worker_id.hex()[:8], args.raylet_address)
    _note_start(entered)
    cw.run_task_execution_loop()


def _note_start(entered: float) -> None:
    """This process's life so far as two pending spans, which the first
    traced task it runs takes home (`tracing.pending`): `worker.spawn`,
    the raylet's `Popen` (its stamp in our environment) to `main`
    entered — the interpreter's start and the import of `ray_tpu` —
    and `worker.boot`, from there to registered with the raylet."""
    from ray_tpu._private import tracing

    who = {"flavor": os.environ.get("RAY_TPU_WORKER_FLAVOR", "cpu"),
           "pid": os.getpid()}
    try:
        spawned = float(os.environ["RAY_TPU_WORKER_SPAWNED_AT"])
    except (KeyError, ValueError):
        pass    # started by hand: no stamp, no spawn span
    else:
        tracing.pending("worker.spawn", spawned, entered, who)
    tracing.pending("worker.boot", entered, time.time(), who)


if __name__ == "__main__":
    main()
