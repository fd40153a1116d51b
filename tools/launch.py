#!/usr/bin/env python
"""Distributed job launcher.

Reference: ``tools/launch.py`` + the dmlc tracker (SURVEY.md §2.3
"launch.py", §4.5: ``launch.py -n 3 -s 1 --launcher local python
script.py`` forks scheduler/servers/workers as local processes with
``DMLC_*`` env — real transport, fake topology).

Supported launchers: ``local`` (fork all roles on this host — the test
topology), ``ssh`` (one worker per host from a hostfile; each host gets
the same DMLC_* rendezvous env), ``mpi`` (delegate process placement to
``mpirun``; ranks derive their DMLC role from ``OMPI_COMM_WORLD_RANK``),
and ``slurm`` (same via ``srun``/``SLURM_PROCID``).  On TPU pods the
heavy data path is XLA collectives over ICI/DCN inside each worker; this
launcher only provides role/rendezvous plumbing, like the reference's
tracker (``dmlc_tracker/{local,ssh,mpi,slurm}.py``).
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _host_chips():
    """(chip count, one_chip_env) from ``mxnet_tpu.context`` — which
    initialises no JAX backend, so this launcher stays off the chips
    and its children can claim them."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from mxnet_tpu.context import host_tpu_chips, one_chip_env
    return host_tpu_chips(), one_chip_env


def _chip_env(i, what):
    """Environment for local child ``i`` that needs a TPU chip: exactly
    chip ``i`` when this host has chips (a chip belongs to one process
    at a time), nothing otherwise."""
    chips, one_chip_env = _host_chips()
    if not chips:
        return {}
    if i >= chips:
        sys.stderr.write("launch: %s %d needs a chip of its own and "
                         "this host has %d\n" % (what, i, chips))
        sys.exit(2)
    return one_chip_env(i)


def _off_chip_env():
    """Environment for a local child that must stay OFF the chips (a
    parameter server, the serving router — they only move host bytes):
    the CPU backend, where this host has chips to protect."""
    return {"JAX_PLATFORMS": "cpu"} if _host_chips()[0] else {}


def launch_local(args, command):
    port = args.port or _free_port()
    base_env = dict(os.environ)
    base_env.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    })
    procs = []

    for i in range(args.num_servers):
        env = dict(base_env)
        env["DMLC_ROLE"] = "server"
        env["DMLC_SERVER_ID"] = str(i)
        env.update(_off_chip_env())
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             "from mxnet_tpu.parallel.dist import run_server; run_server()"],
            env=env))

    for i in range(args.num_workers):
        env = dict(base_env)
        env["DMLC_ROLE"] = "worker"
        env["DMLC_WORKER_ID"] = str(i)
        env.update(_chip_env(i, "worker"))
        procs.append(subprocess.Popen(command, env=env))

    workers = procs[args.num_servers:]
    code = 0
    try:
        for p in workers:
            p.wait()
            code = code or p.returncode
    finally:
        for p in procs[:args.num_servers]:
            p.send_signal(signal.SIGTERM)
    return code


def launch_ssh(args, command):
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    port = args.port or 9091
    root = hosts[0]
    base = [
        "DMLC_PS_ROOT_URI=%s" % root,
        "DMLC_PS_ROOT_PORT=%d" % port,
        "DMLC_NUM_WORKER=%d" % args.num_workers,
        "DMLC_NUM_SERVER=%d" % args.num_servers,
    ]
    server_cmd = (sys.executable + " -c \"from mxnet_tpu.parallel.dist "
                  "import run_server; run_server()\"")
    server_procs = []
    # All servers co-locate on the root host, server i on ROOT_PORT + i —
    # workers key-shard their connections across those ports (run_server).
    for i in range(args.num_servers):
        env_fwd = " ".join(base + ["DMLC_ROLE=server",
                                   "DMLC_SERVER_ID=%d" % i])
        server_procs.append(subprocess.Popen(
            ["ssh", root, env_fwd + " " + server_cmd]))
    worker_procs = []
    for i in range(args.num_workers):
        host = hosts[i % len(hosts)]
        env_fwd = " ".join(base + ["DMLC_ROLE=worker",
                                   "DMLC_WORKER_ID=%d" % i])
        worker_procs.append(subprocess.Popen(
            ["ssh", host, env_fwd + " " + " ".join(command)]))
    code = 0
    try:
        for p in worker_procs:
            p.wait()
            code = code or p.returncode
    finally:
        for p in server_procs:
            p.send_signal(signal.SIGTERM)
    return code


_ROLE_SHIM = (
    "import os,sys,subprocess;"
    "r=int(os.environ.get('OMPI_COMM_WORLD_RANK',"
    "os.environ.get('PMI_RANK',os.environ.get('SLURM_PROCID','0'))));"
    "ns=int(os.environ['DMLC_NUM_SERVER']);"
    "os.environ.update({'DMLC_ROLE':'server','DMLC_SERVER_ID':str(r)}"
    " if r<ns else"
    " {'DMLC_ROLE':'worker','DMLC_WORKER_ID':str(r-ns)});"
    "sys.exit(subprocess.call(sys.argv[1:])"
    " if r>=ns else"
    " __import__('mxnet_tpu.parallel.dist',fromlist=['run_server'])"
    ".run_server())"
)


def _role_shim(env):
    """Bake the rendezvous env into the -c program itself: OpenMPI's
    orted spawns remote ranks with the login-shell environment, NOT
    mpirun's, so env-var forwarding cannot be relied on across nodes."""
    baked = "".join("os.environ[%r]=%r;" % (k, str(v))
                    for k, v in env.items())
    head, rest = _ROLE_SHIM.split(";", 1)
    return head + ";" + baked + rest


def launch_mpi(args, command, runner=None):
    """mpirun/srun launcher (reference: ``dmlc_tracker/mpi.py`` /
    ``slurm.py``).  Spawns num_servers + num_workers ranks; each rank
    derives its DMLC role from its MPI/slurm rank via a tiny shim —
    ranks [0, ns) are servers, the rest workers.  Caveats for multi-node
    allocations: server ranks bind 0.0.0.0 (any node), but
    DMLC_PS_ROOT_URI must name the node where the scheduler places ranks
    [0, ns) — export it before launching (the default, this node's
    hostname, is only right when servers land here).  ``-H/--hostfile``
    is not consulted; placement belongs to mpirun/srun."""
    nproc = args.num_workers + args.num_servers
    port = args.port or 9091
    root = os.environ.get("DMLC_PS_ROOT_URI", socket.gethostname())
    env = {
        "DMLC_PS_ROOT_URI": root,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    }
    if runner is None:
        runner = "srun" if args.launcher == "slurm" else "mpirun"
    # rendezvous env is baked into the shim program (see _role_shim) —
    # launcher-specific -x/--export flags are both insufficient
    # (OpenMPI doesn't forward arbitrary env to remote orted-spawned
    # ranks) and non-portable (MPICH rejects -x)
    cmd = [runner, "-n", str(nproc), sys.executable, "-c",
           _role_shim(env)] + list(command)
    try:
        return subprocess.call(cmd, env={**os.environ, **env})
    except FileNotFoundError:
        sys.stderr.write(
            "%s not found on PATH; the equivalent command is:\n  %s\n"
            % (runner, " ".join(cmd)))
        return 127


def launch_serve(args, command):
    """Role-aware disaggregated-serving launcher (round 15): spawn
    ``--prefill`` + ``--decode`` worker processes running
    ``mxnet_tpu.serving.run_worker`` and the given command as the
    ROUTER process, all wired through ``MXNET_SERVE_*`` env.  The
    router script must build ``DisaggServingCluster(...,
    spawn=False, prefill=<n>, decode=<m>,
    port=int(os.environ["MXNET_SERVE_ROUTER_PORT"]))`` — worker
    processes connect to it exactly like locally-spawned ones, so
    the same protocol scales from this single-host topology to one
    worker per host (run ``run_worker()`` remotely with the env
    pointing at the router).  On a TPU host each worker is given one
    chip of its own and the router the CPU backend (``_chip_env``)."""
    if args.workers_only and not args.port:
        sys.stderr.write(
            "--workers-only: -p/--port must name the LIVE router's "
            "control port (the workers have nothing to rendezvous "
            "with otherwise)\n")
        return 2
    port = args.port or _free_port()
    base_env = dict(os.environ)
    base_env.update({
        "MXNET_SERVE_ROUTER_HOST": args.router_host,
        "MXNET_SERVE_ROUTER_PORT": str(port),
        "MXNET_SERVE_PREFILL": str(args.prefill),
        "MXNET_SERVE_DECODE": str(args.decode),
    })
    router = None
    if not args.workers_only:
        router = subprocess.Popen(
            command, env=dict(base_env, **_off_chip_env()))
    workers = []
    for role, n in (("prefill", args.prefill),
                    ("decode", args.decode)):
        for i in range(n):
            env = dict(base_env)
            env.update(_chip_env(args.worker_start + len(workers),
                                 "serve worker"))
            env["MXNET_SERVE_ROLE"] = role
            # --workers-only joins a LIVE cluster (round 16: the
            # autoscaler's off-host scale-up path — the router's
            # add_worker(role, spawn=False) is waiting for exactly
            # this name): name from --worker-start so the operator
            # matches what the router expects; the default topology
            # numbers workers from 0 as before
            env["MXNET_SERVE_WORKER"] = "%s%d" % (
                role, args.worker_start + i)
            workers.append(subprocess.Popen(
                [sys.executable, "-c",
                 "from mxnet_tpu.serving import run_worker; "
                 "run_worker()"], env=env))
    try:
        if router is not None:
            code = router.wait()
        else:
            code = 0
            for p in workers:
                p.wait()
                code = code or p.returncode
    finally:
        # reap workers in BOTH modes: after the router exits, and on
        # an abnormal exit (Ctrl-C mid-wait) of a --workers-only
        # launcher — otherwise the workers run on unsupervised
        for p in workers:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
    return code


def launch_http(args, command):
    """HTTP front-door launcher (round 20): run the streaming
    HTTP/SSE server ``mxnet_tpu.serving.http_frontend`` as the
    foreground process.  Any extra command tokens are passed through
    to the frontend CLI (``--disagg``, ``--replicas N``, ``--keys
    FILE|JSON``, model geometry flags …); ``-p/--port`` maps onto the
    listening port (default: MXNET_SERVE_HTTP_PORT or OS-assigned,
    printed as JSON at startup).  The demo server builds a
    random-weights model — production embeds
    :class:`mxnet_tpu.serving.HttpFrontend` over its own cluster and
    params (see docs/http_api.md)."""
    command = list(command)
    if command[:1] == ["--"]:              # argparse.REMAINDER keeps it
        command = command[1:]
    # -c entry (not -m): the serving package imports http_frontend at
    # import time, so runpy would warn about the double module object
    cmd = [sys.executable, "-c",
           "import sys; from mxnet_tpu.serving.http_frontend import "
           "main; sys.exit(main(sys.argv[1:]))"]
    if args.port:
        cmd += ["--port", str(args.port)]
    cmd += command
    env = dict(os.environ)
    # the server must import mxnet_tpu wherever the launcher was
    # invoked from — put the repo root on the child's path
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "") \
        if env.get("PYTHONPATH") else repo
    proc = subprocess.Popen(cmd, env=env)
    try:
        return proc.wait()
    except KeyboardInterrupt:
        proc.send_signal(signal.SIGTERM)
        return proc.wait()


def launch_sge(args, command):
    """SGE launcher (reference: ``dmlc_tracker/sge.py``): submit a job
    ARRAY of num_servers + num_workers tasks via ``qsub``; each task
    derives its DMLC role from ``$SGE_TASK_ID`` through the same shim
    the mpi/slurm path uses (task ids [1, ns] are servers, the rest
    workers).  The scheduler host must be reachable from the compute
    nodes via DMLC_PS_ROOT_URI (export before launching, as with mpi)."""
    import tempfile
    nproc = args.num_workers + args.num_servers
    port = args.port or 9091
    root = os.environ.get("DMLC_PS_ROOT_URI", socket.gethostname())
    env = {
        "DMLC_PS_ROOT_URI": root,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    }
    # SGE_TASK_ID is 1-based; translate to the 0-based rank the shim
    # expects (OMPI_COMM_WORLD_RANK is the first var it consults)
    script = "\n".join([
        "#!/bin/sh",
        "#$ -t 1-%d" % nproc,
        "#$ -cwd",
        "#$ -S /bin/sh",
        "export OMPI_COMM_WORLD_RANK=$(($SGE_TASK_ID - 1))",
        " ".join("export %s=%s;" % kv for kv in env.items()),
        "exec %s -c '%s' %s" % (
            sys.executable, _role_shim(env).replace("'", "'\\''"),
            " ".join(command)),
        "",
    ])
    with tempfile.NamedTemporaryFile("w", suffix=".sge.sh",
                                     delete=False) as f:
        f.write(script)
        path = f.name
    cmd = ["qsub", "-sync", "y", path]
    try:
        return subprocess.call(cmd, env={**os.environ, **env})
    except FileNotFoundError:
        sys.stderr.write(
            "qsub not found on PATH; submit the generated job script "
            "yourself:\n  %s\n" % path)
        return 127


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, default=None)
    ap.add_argument("-s", "--num-servers", type=int, default=1)
    ap.add_argument("--launcher", choices=["local", "ssh", "mpi",
                                           "slurm", "sge", "yarn",
                                           "serve", "http"],
                    default="local")
    ap.add_argument("--prefill", type=int, default=1,
                    help="serve launcher: prefill worker processes")
    ap.add_argument("--decode", type=int, default=1,
                    help="serve launcher: decode worker processes")
    ap.add_argument("--workers-only", action="store_true",
                    help="serve launcher: spawn ONLY workers against "
                         "a LIVE router at --router-host:-p (round-16 "
                         "scale-up path: the router must be waiting "
                         "in add_worker(role, spawn=False)); no "
                         "router command is run")
    ap.add_argument("--router-host", default="127.0.0.1",
                    help="serve launcher: router control host the "
                         "workers connect to")
    ap.add_argument("--worker-start", type=int, default=0,
                    help="serve launcher: first worker INDEX per "
                         "role (--workers-only joining a cluster "
                         "that already has prefill0..N-1)")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("-p", "--port", type=int, default=None)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command and args.launcher != "http" \
            and not (args.launcher == "serve" and args.workers_only):
        ap.error("no command given")
    if args.launcher == "http":
        sys.exit(launch_http(args, args.command))
    if args.launcher == "serve":
        sys.exit(launch_serve(args, args.command))
    if args.num_workers is None:
        ap.error("-n/--num-workers is required for this launcher")
    if args.launcher == "local":
        sys.exit(launch_local(args, args.command))
    if args.launcher in ("mpi", "slurm"):
        sys.exit(launch_mpi(args, args.command))
    if args.launcher == "sge":
        sys.exit(launch_sge(args, args.command))
    if args.launcher == "yarn":
        # reference dmlc_tracker/yarn.py drives a Hadoop YARN client jar;
        # there is no YARN runtime in scope to build or test against —
        # deliberate absence, documented rather than stubbed wrong.
        sys.stderr.write(
            "yarn launcher: not supported in this build (needs a Hadoop "
            "cluster + the dmlc-yarn client jar; use ssh/mpi/slurm/sge "
            "against the same DMLC_* contract instead)\n")
        sys.exit(2)
    sys.exit(launch_ssh(args, args.command))


if __name__ == "__main__":
    main()
