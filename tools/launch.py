#!/usr/bin/env python
"""Launch a multi-process distributed job on localhost.

TPU-native rebuild of the reference cluster launcher (reference:
tools/launch.py:31-54 — dmlc-tracker over ssh/mpi/yarn/sge bootstrapping
DMLC_ROLE/DMLC_PS_ROOT_URI). There is no parameter-server role on TPU:
every process is a worker in a jax.distributed job, so the launcher
spawns N copies of the command with COORDINATOR_ADDRESS / NUM_PROCESSES /
PROCESS_ID set (consumed by mxnet_tpu.parallel.dist.init). Multi-host
clusters use the same env contract with your scheduler of choice.

Usage: python tools/launch.py -n 4 python train.py --kv-store dist_sync

``--elastic`` switches to the round-20 multi-host supervisor contract
(mxnet_tpu.parallel.elastic.SupervisorSpec / HostSupervisor): run ONE
launcher per host, all pointed at a shared ``--workdir``; host 0
publishes membership/generation/coordinator in ``control.json``, every
host launches only its own ranks with the machine-checked handshake
env, and a whole-host loss (SIGKILL the launcher tree) re-forms the
survivors at the shrunken world — the exit-75 relaunch protocol,
across hosts:

    python tools/launch.py --elastic --hosts 2 --host-id 0 \\
        --procs-per-host 1 --workdir /shared/job1 python <worker>.py ...
"""
import argparse
import os
import socket
import subprocess
import sys


def find_free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def refuse_shared_chips(parser, n_local):
    """One process per chip: workers inherit ONE environment, so on a
    host with TPUs every one of ``n_local`` workers would open every
    chip, and all but the first would fail or hang. More than one
    worker per host is therefore only launched when the workers are
    pinned to the CPU (``JAX_PLATFORMS=cpu``)."""
    if n_local > 1 and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        parser.error(
            f"refusing to start {n_local} workers on this host: they "
            "would share one environment and each open every TPU chip "
            "(a chip belongs to one process). Run one worker per host, "
            "or set JAX_PLATFORMS=cpu for a CPU job")


def run_elastic(args):
    """One host's share of the multi-host supervisor contract."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir))
    from mxnet_tpu.parallel.elastic import (HostSupervisor,
                                            SupervisorSpec)
    spec = SupervisorSpec(args.workdir, hosts=args.hosts,
                          procs_per_host=args.procs_per_host,
                          lease_s=args.lease_s)
    sup = HostSupervisor(
        spec, args.host_id,
        argv_fn=lambda rank, world, gen, coord: list(args.command),
        timeout_s=args.timeout, max_generations=args.max_generations)
    history = sup.run()
    if args.host_id == 0:
        last = history[-1] if history else {}
        ok = last.get("outcome") == "done"
        print(f"elastic fleet: {len(history)} generation(s), "
              f"outcome={last.get('outcome')}", file=sys.stderr)
        sys.exit(0 if ok else 1)
    sys.exit(0)


def main():
    parser = argparse.ArgumentParser(
        description="launch a local N-process jax.distributed job")
    parser.add_argument("-n", "--num-workers", type=int, default=None,
                        help="number of worker processes")
    parser.add_argument("--coordinator", default=None,
                        help="host:port (default: localhost + free port)")
    parser.add_argument("--elastic", action="store_true",
                        help="run as one host of a multi-host elastic "
                             "supervisor fleet (requires --workdir)")
    parser.add_argument("--hosts", type=int, default=2,
                        help="[elastic] total hosts in the fleet")
    parser.add_argument("--host-id", type=int, default=0,
                        help="[elastic] this host's id (0 = controller)")
    parser.add_argument("--procs-per-host", type=int, default=1,
                        help="[elastic] worker processes per host")
    parser.add_argument("--workdir", default=None,
                        help="[elastic] shared supervisor workdir")
    parser.add_argument("--timeout", type=float, default=240,
                        help="[elastic] per-generation worker timeout")
    parser.add_argument("--max-generations", type=int, default=6,
                        help="[elastic] re-form budget")
    parser.add_argument("--lease-s", type=float, default=None,
                        help="[elastic] host alive-lease TTL")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to run in every worker")
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.elastic:
        if not args.workdir:
            parser.error("--elastic requires --workdir")
        refuse_shared_chips(parser, args.procs_per_host)
        return run_elastic(args)
    if args.num_workers is None:
        parser.error("-n/--num-workers is required without --elastic")
    refuse_shared_chips(parser, args.num_workers)

    coordinator = args.coordinator or f"127.0.0.1:{find_free_port()}"
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update({
            "COORDINATOR_ADDRESS": coordinator,
            "NUM_PROCESSES": str(args.num_workers),
            "PROCESS_ID": str(rank),
        })
        procs.append(subprocess.Popen(args.command, env=env))
    # poll all workers: the first failure kills the rest (a crashed
    # coordinator otherwise leaves siblings blocked in
    # jax.distributed.initialize forever)
    import time
    rc = 0
    live = dict(enumerate(procs))
    while live:
        for rank in list(live):
            code = live[rank].poll()
            if code is None:
                continue
            del live[rank]
            if code != 0:
                print(f"worker {rank} exited with {code}", file=sys.stderr)
                rc = rc or code
                for p in live.values():
                    p.kill()
                for p in live.values():
                    p.wait()
                live = {}
                break
        time.sleep(0.1)
    sys.exit(rc)


if __name__ == "__main__":
    main()
