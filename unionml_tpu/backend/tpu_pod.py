"""TPU pod fleet backend: the real remote-execution target.

Reference parity: the reference deploys by building/pushing a docker image and
registering workflows against a running Flyte admin (``unionml/remote.py:71-161``),
then executes in remote containers. The TPU-native deployment story has no image
build — TPU VMs come with the framework installed (the ``Dockerfile`` at the repo
root is the pod image) — so "deploy" means:

1. package the APP source (the user's module) into the artifact store
   (:mod:`unionml_tpu.backend.store` — GCS for real fleets), and
2. record the workflow spec + TPU resources in the same store.

"Execute" writes the job record to the store and launches one
:mod:`unionml_tpu.backend.pod_worker` per host through a :class:`Transport`:

- :class:`SSHTransport` — real TPU VM fleets (``gcloud compute tpus tpu-vm ssh``
  style; plain ``ssh`` here). Workers pull the job + source from the store, join one
  ``jax.distributed`` mesh (coordinator = host 0), run the workflow SPMD, and host 0
  pushes outputs/status back to the store.
- :class:`LocalShellTransport` — the loopback stand-in: identical command, local
  subprocesses. This is what the backend-contract tests run against, faking exactly
  (and only) the machine boundary.

All lineage/schedule/retry semantics are inherited from
:class:`~unionml_tpu.backend.LocalBackend` — the records simply live in the store,
which :class:`~unionml_tpu.backend.store.StorePath` makes path-compatible.
"""

import io
import json
import os
import posixpath
import shlex
import subprocess
import sys
import tempfile
import zipfile
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from unionml_tpu._logging import logger
from unionml_tpu.backend import Execution, LocalBackend
from unionml_tpu.backend.store import StorePath, store_path
from unionml_tpu.exceptions import BackendError


class LocalShellTransport:
    """Loopback transport: each "host" is a local subprocess.

    The command line, env plumbing, and store round-trip are byte-identical to the
    SSH path — only the machine boundary is faked. With more than one host this
    is a CPU test path: a chip belongs to one process at a time, so several
    loopback workers on one machine cannot each take the same accelerator.
    """

    def __init__(self, host_count: int = 1, scratch: Optional[str] = None):
        self.hosts = [f"loopback-{i}" for i in range(host_count)]
        self.python = sys.executable  # workers run on this machine
        self.coordinator_port: Optional[int] = None  # pick a free local port per job
        self._scratch = scratch or tempfile.mkdtemp(prefix="unionml-pod-")

    def start(self, host_index: int, args: Sequence[str], env: Dict[str, str], log_name: str):
        log_path = Path(self._scratch) / log_name
        log_path.parent.mkdir(parents=True, exist_ok=True)
        with log_path.open("w") as log_file:
            process = subprocess.Popen(
                list(args),
                stdout=log_file,
                stderr=subprocess.STDOUT,
                env={**os.environ, **env},
                cwd=self._scratch,
            )
        return process

    def poll(self, handle) -> Optional[int]:
        return handle.poll()

    def terminate(self, handle, timeout: float = 5.0) -> None:
        if handle.poll() is None:
            handle.terminate()
            try:
                handle.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                handle.kill()
                handle.wait()


class SSHTransport:
    """SSH transport to a TPU VM fleet (one address per host).

    Commands launch detached under ``nohup``; liveness is a ``kill -0`` probe. The
    remote machines must have the framework installed and store credentials available
    (standard TPU VM + GCS service-account setup).
    """

    def __init__(
        self,
        hosts: Sequence[str],
        ssh_options: Sequence[str] = ("-o", "BatchMode=yes"),
        python: str = "python3",
        coordinator_port: int = 8476,
    ):
        """
        :param python: interpreter path ON THE REMOTE HOSTS (the client's
            ``sys.executable`` is meaningless there).
        :param coordinator_port: fixed ``jax.distributed`` coordinator port on host 0
            — client-side free-port probing says nothing about the remote machine.
        """
        if not hosts:
            raise BackendError("SSHTransport requires at least one host address")
        self.hosts = list(hosts)
        self.ssh_options = list(ssh_options)
        self.python = python
        self.coordinator_port: Optional[int] = coordinator_port

    def _ssh(self, host: str, remote_command: str) -> subprocess.CompletedProcess:
        return subprocess.run(
            ["ssh", *self.ssh_options, host, remote_command],
            capture_output=True,
            text=True,
            timeout=120,
        )

    def start(self, host_index: int, args: Sequence[str], env: Dict[str, str], log_name: str):
        host = self.hosts[host_index]
        env_prefix = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
        command = " ".join(shlex.quote(a) for a in args)
        remote = f"{env_prefix} nohup {command} > /tmp/{shlex.quote(log_name)} 2>&1 & echo $!"
        result = self._ssh(host, remote)
        if result.returncode != 0:
            raise BackendError(f"ssh launch on {host} failed: {result.stderr.strip()}")
        return (host, int(result.stdout.strip().splitlines()[-1]))

    def poll(self, handle) -> Optional[int]:
        host, pid = handle
        try:
            result = self._ssh(host, f"kill -0 {pid} 2>/dev/null && echo RUNNING || echo DEAD")
        except (subprocess.TimeoutExpired, OSError) as exc:
            logger.warning("ssh poll to %s failed (%s); treating worker as alive.", host, exc)
            return None
        if result.returncode != 0:
            # transient ssh/network failure is NOT evidence of worker death: a
            # false 'dead' here would tear down a healthy multi-hour fleet.
            # Terminal truth comes from the status file in the store.
            logger.warning(
                "ssh poll to %s returned rc=%d (%s); treating worker as alive.",
                host,
                result.returncode,
                result.stderr.strip(),
            )
            return None
        if "RUNNING" in result.stdout:
            return None
        return 0  # exited; terminal status comes from the store, not the exit code

    def terminate(self, handle, timeout: float = 5.0) -> None:
        host, pid = handle
        self._ssh(host, f"kill {pid} 2>/dev/null; sleep 1; kill -9 {pid} 2>/dev/null; true")


class TPUPodBackend(LocalBackend):
    """Execution backend targeting a TPU VM fleet through a transport + artifact store.

    Implements the full :class:`LocalBackend` protocol (deploy / execute / wait /
    lineage / schedules / retries); state lives in the fsspec store so the client and
    every pod host share one view.
    """

    def __init__(
        self,
        store_url: str,
        transport: Any = None,
        project: Optional[str] = None,
        domain: Optional[str] = None,
        retries: int = 0,
    ):
        self.store_url = store_url
        self.transport = transport or LocalShellTransport()
        self.root = store_path(store_url)
        self.default_project = project or "default-project"
        self.default_domain = domain or "development"
        self.in_process = False
        self.retries = retries
        self._workers: Dict[str, List[Any]] = {}
        self._owned: set = set()
        self._base.mkdir(parents=True, exist_ok=True)

    # ---------------------------------------------------------------- source packaging

    def _source_zip(self, app_version: str) -> StorePath:
        return self._apps_dir / app_version / "source.zip"

    def package_source(self, model: Any, app_version: str) -> Optional[StorePath]:
        """Zip the app's source (module file, or its whole package) into the store.

        The analogue of the reference's fast/"patch" registration zip upload
        (``unionml/remote.py:137-152``): only APP code ships — the framework itself
        is part of the pod image.
        """
        module_file = getattr(model, "_module_file", None)
        if not module_file or not os.path.exists(module_file):
            return None
        module_path = Path(module_file).resolve()
        buffer = io.BytesIO()
        with zipfile.ZipFile(buffer, "w", zipfile.ZIP_DEFLATED) as zf:
            if (module_path.parent / "__init__.py").exists():
                # packaged app: ship the whole top-level package so relative imports
                # survive; base = the directory containing the topmost package
                top = module_path.parent
                while (top.parent / "__init__.py").exists():
                    top = top.parent
                base = top.parent
                for path in sorted(top.rglob("*.py")):
                    zf.write(path, path.relative_to(base))
                rel_module = str(module_path.relative_to(base))
            else:
                zf.write(module_path, module_path.name)
                rel_module = module_path.name
            zf.writestr("__unionml_source__.json", json.dumps({"module_file": rel_module}))
        target = self._source_zip(app_version)
        target.write_bytes(buffer.getvalue())
        logger.info("Packaged app source for version %s (%d bytes)", app_version, buffer.tell())
        return target

    def deploy_workflow(self, model: Any, workflow_name: str, app_version: str, patch: bool = False) -> None:
        super().deploy_workflow(model, workflow_name, app_version, patch=patch)
        # ALWAYS repackage: re-deploying changed app code under the same version
        # (the reference's patch/fast-registration flow) must ship the new source,
        # never a stale zip
        self.package_source(model, app_version)

    def execute(self, model: Any, workflow_name: str, inputs: Dict[str, Any], app_version: Optional[str] = None, schedule_name: Optional[str] = None) -> Execution:
        # dev convenience parity with LocalBackend: undeployed runs package on the
        # fly — under the SAME version the execution's meta will record (the spec's
        # version when deployed, the "dev" fallback otherwise), so _spawn_worker
        # always finds the zip it looks up
        try:
            spec = self.fetch_workflow_spec(workflow_name, app_version)
            version = spec.get("app_version") or "dev"
        except BackendError:
            version = app_version or "dev"
        if not self._source_zip(version).exists():
            self.package_source(model, version)
        return super().execute(model, workflow_name, inputs, app_version=app_version, schedule_name=schedule_name)

    # ---------------------------------------------------------------- worker dispatch

    def _spawn_worker(self, execution: Execution) -> None:
        meta = execution.metadata
        resources = meta.get("resources") or {}
        host_count = int(resources.get("host_count", 1) or 1)
        if host_count > len(self.transport.hosts):
            raise BackendError(
                f"Job requests host_count={host_count} but the transport has "
                f"{len(self.transport.hosts)} host(s)"
            )
        version = meta.get("app_version") or "dev"
        source = self._source_zip(version)
        source_url = str(source) if source.exists() else ""

        coordinator = ""
        if host_count > 1:
            # host 0's address; loopback uses 127.0.0.1 + a locally-probed port,
            # SSH fleets use the transport's fixed coordinator port (a client-side
            # free-port probe says nothing about the remote machine)
            host0 = self.transport.hosts[0]
            address = "127.0.0.1" if host0.startswith("loopback") else host0.split("@")[-1]
            port = getattr(self.transport, "coordinator_port", None)
            if port is None:
                from unionml_tpu.utils import pick_free_port

                port = pick_free_port()
            coordinator = f"{address}:{port}"

        fleet = []
        for host in range(host_count):
            args = [
                getattr(self.transport, "python", sys.executable),
                "-m",
                "unionml_tpu.backend.pod_worker",
                str(execution.directory),
            ]
            if source_url:
                args += ["--source", source_url]
            env = {"UNIONML_POD_HOST_INDEX": str(host)}
            if coordinator:
                env.update(
                    JAX_COORDINATOR_ADDRESS=coordinator,
                    JAX_NUM_PROCESSES=str(host_count),
                    JAX_PROCESS_ID=str(host),
                )
            handle = self.transport.start(host, args, env, log_name=f"{execution.id}-host{host}.log")
            fleet.append(handle)
        self._workers[execution.id] = fleet
        # pod pids are per-remote-host; record the fleet for observability
        (execution.directory / "fleet.json").write_text(
            json.dumps({"hosts": self.transport.hosts[:host_count], "coordinator": coordinator})
        )

    def _terminate_workers(self, execution_id: str, timeout: float = 5.0) -> None:
        for handle in self._workers.pop(execution_id, []):
            self.transport.terminate(handle, timeout=timeout)

    def _reap_dead_worker(self, execution: Execution) -> None:
        fleet = self._workers.get(execution.id)
        if fleet is None:
            return  # not ours: status comes from the store alone
        polls = [self.transport.poll(handle) for handle in fleet]
        if all(p is None for p in polls):
            return
        if any(p is None for p in polls):
            logger.warning("Execution %s: a pod worker died; terminating the fleet.", execution.id)
            self._terminate_workers(execution.id)
        else:
            self._workers.pop(execution.id, None)
        if not execution.is_done:
            (execution.directory / "error.txt").write_text(
                "Pod worker exited without reporting a status (killed or crashed)."
            )
            (execution.directory / "status").write_text("FAILED")


def parse_pod_target(target: str) -> Tuple[Any, Dict[str, str]]:
    """Parse a ``tpu-pod://`` backend target.

    Forms::

        tpu-pod://local?store=file:///tmp/store&hosts=4   -> loopback transport
        tpu-pod://host1,host2?store=gs://bucket/prefix    -> SSH transport

    Returns ``(transport, options)`` where options includes the ``store`` URL.
    """
    from urllib.parse import parse_qs, urlsplit

    parts = urlsplit(target)
    if parts.scheme != "tpu-pod":
        raise BackendError(f"Not a tpu-pod target: {target!r}")
    options = {k: v[0] for k, v in parse_qs(parts.query).items()}
    if "store" not in options:
        raise BackendError("tpu-pod targets require a ?store=<fsspec-url> parameter")
    hosts = [h for h in (parts.netloc or "").split(",") if h]
    if hosts == ["local"] or not hosts:
        transport = LocalShellTransport(host_count=int(options.get("hosts", "1")))
    else:
        transport = SSHTransport(hosts)
    return transport, options
