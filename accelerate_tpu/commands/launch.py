"""`accelerate-tpu launch` — start training across any topology.

Capability parity: reference `commands/launch.py` (1178 LoC) + `utils/launch.py`.
The reference must spawn one process per *device* (torchelastic, xmp.spawn, pdsh);
under JAX SPMD there is exactly **one process per host** and all local chips are
already visible, so launching collapses to: resolve config -> export the
launcher<->library env contract -> run the script. Modes:

  - single host ("LOCAL_MACHINE"): exec the script in-process.
  - TPU pod ("TPU_POD"): each host runs the same command (GKE/gcloud fan-out is
    `tpu-config`'s job, reference `commands/tpu.py`); env carries
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID, or everything
    autodetects from TPU metadata when unset.
  - `--debug_cpu N`: fork N local processes, each a JAX "host" on the CPU
    platform with a localhost coordinator — the reference's `debug_launcher`
    (2-proc gloo CPU) capability, but exercising the *real* multi-process
    collective path over gRPC.

Env contract (consumed by `state.py` / `Accelerator`): ACCELERATE_TPU_MIXED_PRECISION,
ACCELERATE_TPU_GRAD_ACCUM_STEPS, ACCELERATE_TPU_PARALLELISM (dp,fsdp,stage,seq,tp),
ACCELERATE_TPU_DEBUG_MODE.
"""

from __future__ import annotations

import argparse
import os
import runpy
import subprocess
import sys
from pathlib import Path

from .config import LaunchConfig, default_config_file


def launch_env(cfg: LaunchConfig) -> dict[str, str]:
    env: dict[str, str] = {
        "ACCELERATE_TPU_MIXED_PRECISION": cfg.mixed_precision,
        "ACCELERATE_TPU_GRAD_ACCUM_STEPS": str(cfg.gradient_accumulation_steps),
        "ACCELERATE_TPU_PARALLELISM": ",".join(
            str(x)
            for x in (
                cfg.data_parallel_size,
                cfg.fsdp_size,
                cfg.stage_size,
                cfg.sequence_size,
                cfg.tensor_size,
            )
        ),
    }
    if cfg.debug:
        env["ACCELERATE_TPU_DEBUG_MODE"] = "1"
    if cfg.num_processes > 1:
        env["ACCELERATE_TPU_NUM_PROCESSES"] = str(cfg.num_processes)
        env["JAX_NUM_PROCESSES"] = str(cfg.num_processes)
        env["JAX_PROCESS_ID"] = str(cfg.process_id)
        if cfg.coordinator_address:
            env["JAX_COORDINATOR_ADDRESS"] = cfg.coordinator_address
    return env


def _run_script(script: str, script_args: list[str], module: bool) -> None:
    sys.argv = [script] + script_args
    if module:
        runpy.run_module(script, run_name="__main__")
    else:
        runpy.run_path(script, run_name="__main__")


def _child_command(script: str, script_args: list[str], module: bool) -> list[str]:
    """The argv for a child process running the user script — honoring
    ``--module`` the same way the in-process path does (reference
    `utils/launch.py` builds `[sys.executable, "-m", ...]` likewise)."""
    if module:
        return [sys.executable, "-m", script, *script_args]
    return [sys.executable, script, *script_args]


def _debug_cpu_launch(
    n: int,
    script: str,
    script_args: list[str],
    base_env: dict[str, str],
    module: bool = False,
    max_restarts: int = 0,
    monitor_interval: float = 0.5,
    devices_per_process: int = 1,
) -> int:
    """Fork n local JAX 'hosts' over a localhost coordinator (CPU platform).

    With ``max_restarts`` this is the cross-host elastic tier (the torchelastic
    rendezvous role, reference `commands/launch.py:793`): when one host dies,
    its peers crash out of their collectives, every host's supervisor restarts
    its child, and the new generation re-forms at the SAME coordinator address
    — jax.distributed's barrier is the rendezvous. Each generation reads
    ``ACCELERATE_TPU_RESTART_COUNT`` and resumes from the latest checkpoint.
    ``devices_per_process`` > 1 gives each host that many virtual chips — a
    pod-slice topology (N hosts × M chips) without hardware.
    """
    import socket
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    def _spawn(i: int, restarts: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.update(base_env)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
                "JAX_NUM_PROCESSES": str(n),
                "JAX_PROCESS_ID": str(i),
                "ACCELERATE_TPU_NUM_PROCESSES": str(n),
                "ACCELERATE_TPU_RESTART_COUNT": str(restarts),
            }
        )
        if devices_per_process > 1:
            from ..launchers import set_host_device_count_flag

            set_host_device_count_flag(env, devices_per_process)
        return subprocess.Popen(_child_command(script, script_args, module), env=env)

    restarts = 0
    procs = [_spawn(i, restarts) for i in range(n)]
    if max_restarts <= 0:
        rc = 0
        for p in procs:
            rc = p.wait() or rc
        return rc
    while True:
        rcs = [p.poll() for p in procs]
        if all(rc == 0 for rc in rcs):
            return 0
        if any(rc is not None and rc != 0 for rc in rcs):
            if restarts >= max_restarts:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for p in procs:  # same SIGTERM->SIGKILL escalation as restarts
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                        p.wait()
                return next(rc for rc in rcs if rc)
            # one host failed: tear down the generation, restart ALL hosts so
            # the new generation rendezvouses together (elastic semantics)
            restarts += 1
            print(
                f"[accelerate-tpu launch] generation failed (exit codes {rcs}); "
                f"restart {restarts}/{max_restarts}.",
                file=sys.stderr,
            )
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    # torchelastic-style escalation: SIGTERM grace, then SIGKILL
                    p.kill()
                    p.wait()
            procs = [_spawn(i, restarts) for i in range(n)]
        time.sleep(monitor_interval)


def _supervised_launch(
    script: str,
    script_args: list[str],
    base_env: dict[str, str],
    max_restarts: int,
    monitor_interval: float,
    module: bool = False,
) -> int:
    """Failure-detecting supervisor: run the script as a child process and
    restart it on nonzero exit, up to ``max_restarts`` times.

    The reference delegates this to torchelastic (`torch.distributed.run`,
    reference `commands/launch.py:793`; `notebook_launcher` max_restarts /
    monitor_interval, `launchers.py:40-60`). Under one-process-per-host SPMD the
    equivalent is a per-host supervisor: the restarted process re-runs
    `jax.distributed.initialize` and resumes from the latest checkpoint
    (`Accelerator.load_state` — the by_feature/checkpointing.py pattern).
    ``ACCELERATE_TPU_RESTART_COUNT`` tells the script which attempt it is on.
    """
    import time

    restarts = 0
    while True:
        env = dict(os.environ)
        env.update(base_env)
        env["ACCELERATE_TPU_RESTART_COUNT"] = str(restarts)
        proc = subprocess.Popen(_child_command(script, script_args, module), env=env)
        while proc.poll() is None:
            time.sleep(monitor_interval)
        rc = proc.returncode
        if rc == 0:
            return 0
        if restarts >= max_restarts:
            print(
                f"[accelerate-tpu launch] script failed (exit {rc}) after "
                f"{restarts} restart(s); giving up.",
                file=sys.stderr,
            )
            return rc
        restarts += 1
        print(
            f"[accelerate-tpu launch] script failed (exit {rc}); "
            f"restart {restarts}/{max_restarts}.",
            file=sys.stderr,
        )


def _render_env_prefix(env: dict[str, str]) -> str:
    """Render an inline `K=V K=V ...` shell env prefix (one quoting rule for
    every pod mode)."""
    import shlex

    return " ".join(f"{k}={shlex.quote(v)}" for k, v in sorted(env.items()))


def _render_invocation(
    python_executable: str, script: str, script_args: list[str], module: bool
) -> str:
    import shlex

    invoke = f"{python_executable} {'-m ' if module else ''}{shlex.quote(script)}"
    if script_args:
        invoke += " " + " ".join(shlex.quote(a) for a in script_args)
    return invoke


def build_pod_worker_commands(
    workers: list[str],
    script: str,
    script_args: list[str],
    base_env: dict[str, str],
    coordinator_port: int = 8476,
    module: bool = False,
    ssh_user: str | None = None,
    python_executable: str = "python",
) -> list[tuple[str, str, str]]:
    """Build the (ssh_target, remote_command) pair for every pod worker.

    Pure command construction (testable without SSH): worker i gets the full
    launcher<->library env contract inline — JAX_COORDINATOR_ADDRESS pointing
    at worker 0, JAX_NUM_PROCESSES, its JAX_PROCESS_ID — followed by the
    script invocation. Returns [(worker, ssh_target, remote_command), ...].
    Reference role: the xla_dist SSH fan-out (`commands/launch.py:887-943`)
    and the PDSH/hostfile multi-node runner (`:803-853`).
    """
    import shlex

    n = len(workers)
    coordinator = f"{workers[0]}:{coordinator_port}"
    out: list[tuple[str, str, str]] = []
    for i, worker in enumerate(workers):
        env = dict(base_env)
        env.update(
            {
                "JAX_COORDINATOR_ADDRESS": coordinator,
                "JAX_NUM_PROCESSES": str(n),
                "JAX_PROCESS_ID": str(i),
                "ACCELERATE_TPU_NUM_PROCESSES": str(n),
            }
        )
        target = f"{ssh_user}@{worker}" if ssh_user else worker
        out.append((
            worker,
            target,
            f"{_render_env_prefix(env)} "
            f"{_render_invocation(python_executable, script, script_args, module)}",
        ))
    return out


def _pod_ssh_launch(
    workers: list[str],
    script: str,
    script_args: list[str],
    base_env: dict[str, str],
    coordinator_port: int,
    module: bool = False,
    ssh_user: str | None = None,
    ssh_executable: str = "ssh",
    python_executable: str = "python",
) -> int:
    """SSH-fan the per-host launch to every worker and wait for all of them.

    One `ssh worker '<env contract> python script.py ...'` per host, started
    concurrently; the first worker hosts the jax.distributed coordinator. A
    nonzero exit anywhere is the job's exit (the peers crash out of their
    collectives, exactly like a failed NCCL rank). ``ssh_executable`` is
    swappable so the fan-out path itself is rehearsable without real SSH
    (`--ssh_executable ./local_shim.sh` in tests; reference rehearses its
    PDSH runner the same way).
    """
    cmds = build_pod_worker_commands(
        workers, script, script_args, base_env,
        coordinator_port=coordinator_port, module=module, ssh_user=ssh_user,
        python_executable=python_executable,
    )
    procs = []
    for worker, target, remote in cmds:
        print(f"[accelerate-tpu launch] {worker}: {remote}", file=sys.stderr)
        procs.append(subprocess.Popen([ssh_executable, target, remote]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def _gcloud_pod_launch(args: argparse.Namespace, cfg: LaunchConfig) -> int:
    """Single-command Cloud TPU pod bringup: gcloud ssh --worker=all runs the
    same `accelerate-tpu launch` on every pod VM (reference `tpu_pod_launcher`
    role, `commands/launch.py:887-943`, minus xla_dist).

    The resolved run plan travels as EXPLICIT inner-launch flags, not env:
    the inner launch recomputes its env from its own flags (flags > env >
    config), so an env prefix would be clobbered. Crucially, NO
    JAX_PROCESS_ID/JAX_COORDINATOR_ADDRESS is forwarded — every VM must
    autodetect its own identity from the TPU metadata (forwarding the caller's
    process id 0 to all workers would collide the rendezvous)."""
    import shlex

    inner_flags = [
        "--mixed_precision", cfg.mixed_precision,
        "--gradient_accumulation_steps", str(cfg.gradient_accumulation_steps),
        "--data_parallel_size", str(cfg.data_parallel_size),
        "--fsdp_size", str(cfg.fsdp_size),
        "--tensor_size", str(cfg.tensor_size),
        "--sequence_size", str(cfg.sequence_size),
        "--stage_size", str(cfg.stage_size),
    ]
    if args.module:
        inner_flags.append("--module")
    if args.compilation_cache_dir:
        inner_flags += ["--compilation_cache_dir", args.compilation_cache_dir]
    inner = (
        "accelerate-tpu launch "
        + " ".join(shlex.quote(f) for f in inner_flags)
        + f" {shlex.quote(args.training_script)}"
    )
    if args.training_script_args:
        inner += " " + " ".join(shlex.quote(a) for a in args.training_script_args)
    # one gcloud-invocation builder for both surfaces (tpu-config + launch)
    from .tpu import build_gcloud_command

    cmd = build_gcloud_command(args.tpu_name, args.zone, command=inner)
    print("[accelerate-tpu launch] " + " ".join(cmd), file=sys.stderr)
    return subprocess.run(cmd).returncode


def launch_command(args: argparse.Namespace) -> None:
    cfg = LaunchConfig.from_yaml(Path(args.config_file) if args.config_file else None)
    # CLI overrides (flag > env > config file)
    for attr in (
        "num_processes",
        "process_id",
        "coordinator_address",
        "mixed_precision",
        "gradient_accumulation_steps",
        "data_parallel_size",
        "fsdp_size",
        "tensor_size",
        "sequence_size",
        "stage_size",
    ):
        value = getattr(args, attr, None)
        if value is not None:
            setattr(cfg, attr, value)
    if args.debug:
        cfg.debug = True
    if args.main_process_ip:
        cfg.coordinator_address = (
            f"{args.main_process_ip}:{args.main_process_port or 8476}"
        )

    env = launch_env(cfg)
    if args.compilation_cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = args.compilation_cache_dir
    # explicit pod flags beat a saved AMAZON_SAGEMAKER compute_environment;
    # --sagemaker combined with a pod flag is a contradiction, not a precedence
    if args.hostfile:
        if args.workers:
            raise SystemExit("--workers and --hostfile are mutually exclusive")
        # DeepSpeed hostfile shape: "hostname slots=N" per line; SPMD runs one
        # process per host so the slot count is informational only
        hosts = []
        with open(args.hostfile) as f:
            for line in f:
                line = line.strip()
                if line and not line.startswith("#"):
                    hosts.append(line.split()[0])
        if not hosts:
            raise SystemExit(f"hostfile {args.hostfile} contains no hosts")
        args.workers = ",".join(hosts)
    if args.sagemaker and (args.workers or args.tpu_name):
        raise SystemExit("--sagemaker and --workers/--tpu_name are mutually exclusive")
    if args.sagemaker or (
        cfg.compute_environment == "AMAZON_SAGEMAKER"
        and not (args.workers or args.tpu_name)
    ):
        from .sagemaker import from_dict, sagemaker_launcher

        sys.exit(sagemaker_launcher(from_dict(cfg.sagemaker), args, env))
    if args.workers and args.tpu_name:
        raise SystemExit("--workers and --tpu_name are mutually exclusive pod modes")
    if args.workers:
        workers = [w.strip() for w in args.workers.split(",") if w.strip()]
        rc = _pod_ssh_launch(
            workers, args.training_script, args.training_script_args, env,
            coordinator_port=args.coordinator_port,
            module=args.module,
            ssh_user=args.ssh_user,
            ssh_executable=args.ssh_executable,
            python_executable=args.python_executable,
        )
        sys.exit(rc)
    if args.tpu_name:
        if not args.zone:
            raise SystemExit("--tpu_name requires --zone")
        sys.exit(_gcloud_pod_launch(args, cfg))
    if args.debug_cpu:
        rc = _debug_cpu_launch(
            args.debug_cpu, args.training_script, args.training_script_args, env,
            module=args.module,
            max_restarts=args.max_restarts,
            monitor_interval=args.monitor_interval,
            devices_per_process=args.devices_per_process,
        )
        sys.exit(rc)
    if args.max_restarts:
        rc = _supervised_launch(
            args.training_script,
            args.training_script_args,
            env,
            max_restarts=args.max_restarts,
            monitor_interval=args.monitor_interval,
            module=args.module,
        )
        sys.exit(rc)
    os.environ.update(env)
    _run_script(args.training_script, args.training_script_args, module=args.module)


def add_parser(subparsers) -> None:
    p = subparsers.add_parser("launch", help="launch a training script")
    p.add_argument("--config_file", default=None)
    p.add_argument("--num_processes", "--num_machines", type=int, default=None,
                   dest="num_processes",
                   help="number of hosts (alias --num_machines: one process "
                        "per host under SPMD, so machines == processes)")
    p.add_argument("--process_id", "--machine_rank", type=int, default=None,
                   dest="process_id", help="this host's index (alias --machine_rank)")
    p.add_argument("--coordinator_address", default=None, help="host0:port")
    p.add_argument("--main_process_ip", default=None,
                   help="coordinator host (reference alias; combined with "
                        "--main_process_port into the coordinator address)")
    p.add_argument("--main_process_port", type=int, default=None,
                   help="coordinator port for --main_process_ip")
    p.add_argument("--compilation_cache_dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(JAX_COMPILATION_CACHE_DIR; the torch.compile "
                        "cache-dir analogue)")
    p.add_argument("--mixed_precision", default=None, choices=["no", "bf16", "fp16", "fp8"])
    p.add_argument("--gradient_accumulation_steps", type=int, default=None)
    p.add_argument("--data_parallel_size", "--dp", type=int, default=None, dest="data_parallel_size")
    p.add_argument("--fsdp_size", "--fsdp", type=int, default=None, dest="fsdp_size")
    p.add_argument("--tensor_size", "--tp", type=int, default=None, dest="tensor_size")
    p.add_argument("--sequence_size", "--sp", type=int, default=None, dest="sequence_size")
    p.add_argument("--stage_size", "--pp", type=int, default=None, dest="stage_size")
    p.add_argument("--debug", action="store_true", help="enable collective shape verification")
    p.add_argument("--debug_cpu", type=int, default=None, metavar="N",
                   help="fork N local CPU 'hosts' over a localhost coordinator")
    p.add_argument("--devices_per_process", type=int, default=1, metavar="M",
                   help="with --debug_cpu: give each host M virtual chips "
                        "(rehearse an N-host x M-chip pod slice without hardware)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="restart the script on failure up to N times "
                        "(torchelastic analogue; resume via load_state)")
    p.add_argument("--monitor_interval", type=float, default=1.0,
                   help="seconds between child liveness checks under --max_restarts")
    p.add_argument("--module", action="store_true", help="treat script as a python module")
    # -------- first-class multi-host pod bringup (reference launch.py:803-943)
    p.add_argument("--workers", default=None, metavar="HOST1,HOST2,...",
                   help="SSH-fan the launch to these hosts; worker 0 hosts the "
                        "jax.distributed coordinator")
    p.add_argument("--hostfile", default=None, metavar="PATH",
                   help="PDSH/DeepSpeed-style hostfile (one host per line, "
                        "'slots=N' annotations ignored — one process per host "
                        "under SPMD); alternative to --workers")
    p.add_argument("--coordinator_port", type=int, default=8476,
                   help="with --workers: port for the coordinator on worker 0")
    p.add_argument("--ssh_user", default=None, help="with --workers: ssh as this user")
    p.add_argument("--ssh_executable", default="ssh",
                   help="with --workers: ssh command to use (swap in a shim to "
                        "rehearse the fan-out locally)")
    p.add_argument("--python_executable", default="python",
                   help="with --workers: interpreter to run on each host")
    p.add_argument("--tpu_name", default=None,
                   help="Cloud TPU pod name: run this same launch on every pod "
                        "VM via gcloud ssh --worker=all")
    p.add_argument("--zone", default=None, help="GCE zone for --tpu_name")
    p.add_argument("--sagemaker", action="store_true",
                   help="submit the script as an Amazon SageMaker training job "
                        "(config's sagemaker section provides role/instances)")
    p.add_argument("--dry_run", action="store_true",
                   help="with --sagemaker: print the job spec without submitting")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    p.set_defaults(func=launch_command)


def main() -> None:
    parser = argparse.ArgumentParser("accelerate-tpu-launch")
    sub = parser.add_subparsers(dest="_cmd")
    add_parser(sub)
    argv = sys.argv[1:]
    if argv and argv[0] != "launch":
        argv = ["launch", *argv]
    args = parser.parse_args(argv)
    args.func(args)


if __name__ == "__main__":
    main()
