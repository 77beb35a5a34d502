"""In-process launchers.

Capability parity: reference `launchers.py` (302 LoC) — `notebook_launcher`
(start distributed training from a notebook) and `debug_launcher` (multi-process
CPU run for tests).

TPU-native: inside a notebook on a TPU VM the devices are already attached to
this process, so single-host `notebook_launcher` just runs the function
(per-core forking — xmp.spawn — is a torch_xla artifact with no JAX equivalent
or need). ``num_processes`` > 1 forks real worker processes that *inherit the
notebook's interpreter state* — closures and cell-defined functions launch
without being importable, the property that distinguishes the notebook path
from `debug_launcher`'s importable-script contract. It is refused where the
workers could land on a TPU: each would open every local chip, and a chip
belongs to one process. `debug_launcher` spawns
fresh OS processes, each a JAX "host" on the CPU platform with a localhost
coordinator — exercising the true multi-process collective path.
"""

from __future__ import annotations

import inspect
import os
import subprocess
import sys
import tempfile
import textwrap
import time
import traceback
from typing import Callable


def set_host_device_count_flag(env: dict, n_devices: int) -> None:
    """Point a child's ``XLA_FLAGS`` at ``n_devices`` virtual host-CPU chips,
    replacing any existing count flag — the one place this flag is spelled for
    child envs (the CLI launcher and debug_launcher both route here)."""
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)


def _jax_backends_initialized() -> bool:
    """True once this process has materialized any XLA backend. Forking after
    that point hands children dead device handles (the reference's analogous
    guard errors when CUDA is initialized — `launchers.py:are_libraries_initialized`
    role), so the launcher refuses rather than deadlocking."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(getattr(xb, "_backends", None))


def _workers_could_land_on_tpu() -> bool:
    """Whether processes forked from here could open TPU chips, judged without
    touching a backend (that would make forking impossible): the platform is
    pinned to something else, or it is left to JAX and libtpu is installed."""
    import importlib.util

    import jax

    pinned = jax.config.jax_platforms  # JAX_PLATFORMS, unless set in code
    if pinned:
        return "tpu" in pinned.split(",")
    return importlib.util.find_spec("libtpu") is not None


def _notebook_worker(function, args, env: dict) -> None:
    """Forked child body: point the JAX env contract at the coordinator BEFORE
    any backend init, run, and `os._exit` so IPython atexit hooks inherited
    from the notebook kernel never fire in the worker."""
    os.environ.update(env)
    try:
        function(*args)
    except BaseException:
        traceback.print_exc()
        os._exit(1)
    os._exit(0)


def notebook_launcher(
    function: Callable,
    args: tuple = (),
    num_processes: int | None = None,
    mixed_precision: str = "no",
    use_port: str = "29500",
    master_addr: str = "127.0.0.1",
    node_rank: int = 0,
    num_nodes: int = 1,
    max_restarts: int = 0,
    monitor_interval: float = 0.1,
    **kwargs,
) -> None:
    """Start training from a notebook (reference `launchers.py:40-266`).

    On a TPU VM every local chip is already attached to THIS process, so the
    single-host case needs no elastic worker spawn: the function runs inline
    over all devices (the reference's per-core xmp.spawn is a torch_xla
    artifact). Passing ``num_processes`` > 1 forks that many real JAX worker
    processes over a coordinator at ``master_addr:use_port`` — because they are
    *forked*, the function may be a closure defined in a notebook cell, the
    reference's signature notebook capability. ``num_nodes``/``node_rank``
    extend the rendezvous across machines running the same notebook code
    (process ids are offset by ``node_rank * num_processes``). A crashed
    generation is re-launched up to ``max_restarts`` times, mirroring the
    reference's elastic-agent restarts; the parent polls children every
    ``monitor_interval`` seconds and tears the generation down as soon as any
    worker fails. ``use_port="0"`` picks a free port (single-node only).
    """
    os.environ.setdefault("ACCELERATE_TPU_MIXED_PRECISION", mixed_precision)
    if (num_processes is None or num_processes <= 1) and num_nodes <= 1:
        function(*args)
        return
    num_processes = num_processes or 1
    if os.environ.get("ACCELERATE_TPU_NUM_PROCESSES"):
        raise RuntimeError(
            "notebook_launcher cannot nest inside an already-launched distributed job."
        )
    if num_nodes > 1 and str(use_port) == "0":
        raise ValueError(
            "use_port='0' (ephemeral) would make each node pick a different "
            "coordinator port and hang the rendezvous; pass an explicit port "
            "for multi-node launches."
        )
    if num_processes > 1 and _workers_could_land_on_tpu():
        raise RuntimeError(
            f"notebook_launcher(num_processes={num_processes}) forks workers that "
            "would each open every local TPU chip, and a chip belongs to one "
            "process: the first worker holds them and the rest fail or hang. One "
            "process drives all local chips, so call the function with "
            "num_processes=1 — or pin JAX_PLATFORMS=cpu to fork CPU workers."
        )
    if _jax_backends_initialized():
        raise RuntimeError(
            "JAX devices are already initialized in this process; forked workers "
            "would inherit dead device handles. Restart the notebook kernel and "
            "call notebook_launcher before running any JAX computation."
        )
    import multiprocessing

    try:
        ctx = multiprocessing.get_context("fork")
    except ValueError:
        if num_nodes > 1:
            raise RuntimeError(
                "multi-node notebook_launcher requires the fork start method "
                "(unavailable on this OS); a single-node fallback would form a "
                "wrong-sized world and hang the other nodes."
            )
        # no fork on this OS: the spawn fallback re-loads the function's module
        # file in each child (debug_launcher's runpy path), so cell-defined
        # closures (the advertised API) cannot work — check debug_launcher's
        # actual requirements up front and fail naming the real limitation.
        mod = inspect.getmodule(function)
        qualname = getattr(function, "__qualname__", getattr(function, "__name__", ""))
        loadable = (
            mod is not None
            and hasattr(mod, "__file__")
            and "." not in qualname
            and "<locals>" not in qualname
        )
        if not loadable:
            raise RuntimeError(
                "notebook_launcher requires the 'fork' start method for "
                "notebook-cell functions, which this OS does not provide. The "
                f"spawn fallback re-loads the function's module file, but "
                f"{qualname!r} is not a module-level function in a file. Move "
                "it to module level in a .py file, or run on a fork-capable OS."
            )
        debug_launcher(function, args=args, num_processes=num_processes, platform=None)
        return

    world = num_nodes * num_processes
    for attempt in range(max_restarts + 1):
        port = use_port
        if str(use_port) == "0":
            import socket

            with socket.socket() as s:
                s.bind((master_addr, 0))
                port = str(s.getsockname()[1])
        procs = []
        for i in range(num_processes):
            env = {
                "JAX_COORDINATOR_ADDRESS": f"{master_addr}:{port}",
                "JAX_NUM_PROCESSES": str(world),
                "JAX_PROCESS_ID": str(node_rank * num_processes + i),
                "ACCELERATE_TPU_NUM_PROCESSES": str(world),
                "ACCELERATE_TPU_MIXED_PRECISION": mixed_precision,
            }
            p = ctx.Process(target=_notebook_worker, args=(function, args, env))
            p.start()
            procs.append(p)
        try:
            failed = None
            while failed is None and any(p.is_alive() for p in procs):
                time.sleep(monitor_interval)
                failed = next(
                    (p for p in procs if p.exitcode not in (None, 0)), None
                )
            if failed is None:
                failed = next((p for p in procs if p.exitcode not in (None, 0)), None)
        except (KeyboardInterrupt, SystemExit):
            for p in procs:
                if p.is_alive():
                    p.terminate()
            for p in procs:
                p.join()
            raise
        if failed is not None:
            for p in procs:
                if p.is_alive():
                    p.terminate()
        for p in procs:
            p.join()
        if failed is None:
            return
        if attempt == max_restarts:
            raise RuntimeError(
                f"notebook_launcher worker {procs.index(failed)} failed with exit code "
                f"{failed.exitcode} (after {attempt} restart(s))"
            )


def debug_launcher(
    function: Callable,
    args: tuple = (),
    num_processes: int = 2,
    devices_per_process: int = 1,
    platform: str | None = "cpu",
) -> None:
    """Fork ``num_processes`` 'hosts' over a localhost coordinator and run
    ``function(*args)`` in each (reference `launchers.py:269` — 2-proc gloo CPU).

    ``platform="cpu"`` (the default, the debug tier) forces each child onto the
    host-CPU backend; ``platform=None`` inherits the parent's platform — used
    by `notebook_launcher` so notebook-spawned workers keep their accelerator.
    ``devices_per_process`` > 1 gives each CPU child that many virtual devices
    (host-platform multiplexing) — a pod-slice topology (N hosts × M chips)
    without hardware.

    The function must be importable (defined in a module, not a closure): each
    child imports it by qualified name, mirroring how torch's spawn pickles.
    """
    import socket

    module = inspect.getmodule(function)
    if module is None or not hasattr(module, "__file__"):
        raise ValueError("debug_launcher requires a function defined in an importable module file")
    fn_name = function.__qualname__
    if "." in fn_name or "<locals>" in fn_name:
        raise ValueError("debug_launcher requires a module-level function")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    runner = textwrap.dedent(
        f"""
        import runpy, sys
        from accelerate_tpu.state import PartialState
        PartialState()  # initialize jax.distributed from the env contract first
        ns = runpy.run_path({module.__file__!r})
        ns[{fn_name!r}](*{args!r})
        """
    )
    procs = []
    for i in range(num_processes):
        env = dict(os.environ)
        env.update(
            {
                "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
                "JAX_NUM_PROCESSES": str(num_processes),
                "JAX_PROCESS_ID": str(i),
                "ACCELERATE_TPU_NUM_PROCESSES": str(num_processes),
            }
        )
        if platform is not None:
            env["JAX_PLATFORMS"] = platform
        if platform == "cpu" or devices_per_process > 1:
            # always pin the count: an inherited parent XLA_FLAGS (e.g. a test
            # host forcing 8 virtual devices) would otherwise multiply each
            # child's device count and silently change the data-axis topology
            set_host_device_count_flag(env, devices_per_process)
        procs.append(subprocess.Popen([sys.executable, "-c", runner], env=env))
    codes = [p.wait() for p in procs]
    if any(codes):
        raise RuntimeError(f"debug_launcher children failed with exit codes {codes}")
