"""Worker entrypoint: rehydrate the app module and execute a workflow.

This is the process/machine boundary of the backend — the analogue of the reference's
task resolver running inside a remote container (``unionml/task_resolver.py:16-31``):
the worker receives an execution directory containing ``meta.json`` with the app's
``(module, variable)`` address, re-imports the module (which re-runs the ``Dataset``/
``Model`` decorators), rebuilds the named workflow, and executes it.

On a multi-host TPU slice every host runs this same entrypoint; host 0 writes outputs.
``jax.distributed`` initialization happens here (before any jax computation) when the
job's resource spec declares ``host_count > 1``.
"""

import json
import pickle
import sys
from pathlib import Path
from typing import Any, Dict


def _resolve_workflow(model: Any, workflow_name: str):
    """Map a workflow name back to its factory on the rehydrated model object."""
    factories = {
        model.train_workflow_name: model.train_workflow,
        model.predict_workflow_name: model.predict_workflow,
        model.predict_from_features_workflow_name: model.predict_from_features_workflow,
    }
    try:
        return factories[workflow_name]()
    except KeyError:
        raise ValueError(
            f"Workflow {workflow_name!r} is not one of {sorted(factories)} for model {model.name!r}"
        ) from None


def _coerce_inputs(workflow, inputs: Dict[str, Any]) -> Dict[str, Any]:
    """Rebuild typed kwargs dataclasses from the plain-dict wire format."""
    coerced = {}
    for name, annotation in workflow.input_types.items():
        value = inputs.get(name)
        if (
            isinstance(value, dict)
            and isinstance(annotation, type)
            and hasattr(annotation, "from_dict")
        ):
            coerced[name] = annotation.from_dict(value)
        elif name in inputs:
            coerced[name] = value
    return coerced


def run_workflow_for_model(model: Any, workflow_name: str, inputs: Dict[str, Any]) -> Dict[str, Any]:
    """Execute a named workflow and map positional results to named outputs.

    Inputs are wire-decoded (state-dict-encoded model objects rebuilt via the app's
    init) and outputs wire-encoded back — see ``unionml_tpu.backend.wire_encode_value``.
    """
    from unionml_tpu.backend import _plain_inputs, wire_decode_value

    workflow = _resolve_workflow(model, workflow_name)
    inputs = {key: wire_decode_value(value, model) for key, value in inputs.items()}
    result = workflow(**_coerce_inputs(workflow, inputs))
    names = workflow.output_names
    if len(names) == 1:
        return _plain_inputs({names[0]: result})
    return _plain_inputs(dict(zip(names, result)))


def run_execution(execution_dir: Path, module_file_override: str = None) -> int:
    """Run one execution from its (local or store-backed) directory.

    ``module_file_override``: local path of the app module when the recorded
    ``module_file`` belongs to another machine (pod workers extract the shipped
    source zip and pass its location — see ``unionml_tpu.backend.pod_worker``).
    """
    from unionml_tpu._logging import logger
    from unionml_tpu.tracker import load_tracked_instance
    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()
    with (execution_dir / "meta.json").open() as f:
        raw = f.read()
    meta = json.loads(raw.decode() if isinstance(raw, bytes) else raw)
    if module_file_override:
        meta["module_file"] = module_file_override
    (execution_dir / "status").write_text("RUNNING")

    host_index = 0
    try:
        primary = True
        resources = meta.get("resources") or {}
        if (resources.get("host_count") or 1) > 1:
            import jax

            from unionml_tpu.parallel.distributed import initialize_distributed

            # strict: a silent single-process fallback would run N uncoordinated
            # copies of the job, each believing it is primary
            initialize_distributed(strict=True)
            primary = jax.process_index() == 0
            host_index = jax.process_index()

        model = load_tracked_instance(meta["app_module"], meta["app_variable"], meta.get("module_file"))
        with (execution_dir / "inputs.pkl").open("rb") as f:
            inputs = pickle.load(f)
        outputs = run_workflow_for_model(model, meta["workflow_name"], inputs)
        # every host runs the SPMD body; only host 0 owns outputs and terminal status
        if primary:
            with (execution_dir / "outputs.pkl").open("wb") as f:
                pickle.dump(outputs, f)
            (execution_dir / "status").write_text("SUCCEEDED")
        return 0
    except Exception as exc:  # record failure for the waiting client
        logger.exception("Worker failed for execution %s", meta.get("execution_id"))
        (execution_dir / f"error-host{host_index}.txt").write_text(repr(exc))
        status_file = execution_dir / "status"
        # never demote a completed job: host 0 may have already written SUCCEEDED
        # before a secondary host failed post-hoc
        if not (status_file.exists() and status_file.read_text().strip() == "SUCCEEDED"):
            (execution_dir / "error.txt").write_text(repr(exc))
            status_file.write_text("FAILED")
        return 1


def main() -> None:
    if len(sys.argv) != 2:
        print("usage: python -m unionml_tpu.backend.worker <execution_dir>", file=sys.stderr)
        raise SystemExit(2)
    raise SystemExit(run_execution(Path(sys.argv[1])))


if __name__ == "__main__":
    main()
