"""Multi-process launcher — the ``torch.distributed.run`` / ``mp.spawn`` twin.

The reference launches one of two ways: ``python -m torch.distributed.run
--nproc_per_node 2 --use_env test_data_parallelism.py`` (reference
README.md:13) or an in-process ``mp.spawn(training_function, nprocs=
world_size, join=True)`` (test_model_parallelism.py:333-335). This launcher
is their one TPU-native replacement: it spawns N OS processes, wires the
``jax.distributed.initialize`` rendezvous env that ``comms.bootstrap``
consumes (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
``JAX_PROCESS_ID`` — the RANK/WORLD_SIZE/MASTER_ADDR analogue), prefixes
each child's output with its rank, and tears the whole job down on the
first failure (the reference's ``join=True`` only *propagates* a crash;
here sibling processes are also terminated so a dead rank can't leave the
rest deadlocked in a collective).

    # 4 cooperating processes on this host (e.g. CPU-mesh simulation):
    python -m pytorch_distributed_training_tpu.cli.launch --nprocs 4 -- \
        python -m pytorch_distributed_training_tpu.cli.train_dp --model tiny

On real TPU pods the infra usually starts one process per host already —
then no launcher is needed; ``comms.bootstrap.initialize`` picks the env up
directly. This command is for single-host multi-process runs (and for
exercising true multi-process rendezvous + Gloo/ICI collectives in tests).
"""

from __future__ import annotations

import argparse
import os
import re
import signal
import socket
import subprocess
import sys
import threading


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _stream(proc: subprocess.Popen, rank: int) -> None:
    for line in proc.stdout:  # type: ignore[union-attr]
        sys.stdout.write(f"[rank {rank}] {line.decode(errors='replace')}")
        sys.stdout.flush()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        usage="python -m pytorch_distributed_training_tpu.cli.launch "
        "--nprocs N [options] -- <command...>",
    )
    p.add_argument("--nprocs", type=int, required=True,
                   help="number of processes to spawn")
    p.add_argument("--coordinator", default=None,
                   help="host:port for rendezvous (default: 127.0.0.1:<free>)")
    p.add_argument("--devices-per-proc", type=int, default=0,
                   help="force this many virtual CPU devices per process "
                        "(sets JAX_PLATFORMS=cpu + "
                        "--xla_force_host_platform_device_count; 0 = leave "
                        "the child environment alone, e.g. real TPU hosts)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="command to run in every process (prefix with --)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        raise SystemExit("no command given (append: -- python -m ... )")
    if args.devices_per_proc == 0 and args.nprocs > 1:
        # one process drives every chip of a TPU host; N local processes
        # left to the default environment would all claim the same chips
        from pytorch_distributed_training_tpu.utils.chips import (
            host_tpu_chips,
        )

        if host_tpu_chips():
            raise SystemExit(
                f"--nprocs {args.nprocs} on a TPU host: a chip belongs to "
                f"one process at a time and one process drives all of this "
                f"host's chips — run the command directly, or pass "
                f"--devices-per-proc N for the CPU-mesh simulation"
            )
    coordinator = args.coordinator or f"127.0.0.1:{_free_port()}"

    procs: list[subprocess.Popen] = []
    threads: list[threading.Thread] = []
    for rank in range(args.nprocs):
        env = dict(os.environ)
        env["JAX_COORDINATOR_ADDRESS"] = coordinator
        env["JAX_NUM_PROCESSES"] = str(args.nprocs)
        env["JAX_PROCESS_ID"] = str(rank)
        if args.devices_per_proc > 0:
            # CPU-mesh simulation: pin the platform and the virtual device
            # count (the same redirection tests/conftest.py applies)
            env["JAX_PLATFORMS"] = "cpu"
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                env.get("XLA_FLAGS", ""),
            )
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.devices_per_proc}"
            ).strip()
        proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT
        )
        procs.append(proc)
        t = threading.Thread(target=_stream, args=(proc, rank), daemon=True)
        t.start()
        threads.append(t)

    rc = 0
    try:
        remaining = set(range(args.nprocs))
        while remaining:
            for rank in list(remaining):
                p = procs[rank]
                try:
                    p.wait(timeout=0.2)
                except subprocess.TimeoutExpired:
                    continue
                remaining.discard(rank)
                if p.returncode != 0:
                    rc = p.returncode
                    # 75 = faults.preemption.RESUMABLE_EXIT_CODE: the rank
                    # checkpointed and exited gracefully — relaunching with
                    # --resume continues it; don't treat it as a crash
                    note = (
                        " (preempted: emergency checkpoint written, "
                        "relaunch with --resume)"
                        if p.returncode == 75
                        else ""
                    )
                    sys.stderr.write(
                        f"[launch] rank {rank} exited with {p.returncode}"
                        f"{note}; "
                        f"terminating {len(remaining)} remaining process(es)\n"
                    )
                    for other in remaining:
                        procs[other].terminate()
                    for other in remaining:
                        try:
                            procs[other].wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            procs[other].kill()
                    remaining = set()
                    break
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        for p in procs:  # same escalation as the sibling-failure path: a
            # rank stuck in a collective ignores SIGINT forever
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
        rc = 130
    for t in threads:
        t.join(timeout=5)
    return rc


if __name__ == "__main__":
    sys.exit(main())
