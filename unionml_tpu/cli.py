"""Command-line interface: ``unionml-tpu`` (click-based).

Reference parity: the typer app at ``unionml/cli.py:19-331`` — the same command set
(``init``, ``deploy``, ``activate-schedules``, ``deactivate-schedules``, ``train``,
``predict``, listings, ``fetch-model``, ``fetch-predictions``, ``serve``) plus a
``scheduler`` command running the in-framework cron loop (the reference delegates
firing to Flyte). ``serve`` hosts the native aiohttp app with the resident compiled
predictor instead of wrapping uvicorn; ``--model-path`` still lands in
``UNIONML_MODEL_PATH`` (``cli.py:285-320`` behavior).

Note: the reference's deactivate command calls ``remote_activate_schedules``
(``cli.py:124`` — an upstream bug); this implementation deactivates.
"""

import json
import os
import sys
from pathlib import Path
from typing import Optional

import click

from unionml_tpu._logging import logger


def _load_model(app: str):
    from unionml_tpu.remote import get_model

    return get_model(app)


def _parse_json_opt(value: Optional[str], flag: str) -> dict:
    if not value:
        return {}
    try:
        return json.loads(value)
    except json.JSONDecodeError as exc:
        raise click.BadParameter(f"{flag} must be valid JSON: {exc}") from exc


@click.group(name="unionml-tpu")
def app() -> None:
    """unionml-tpu: TPU-native model training, serving, and deployment."""


@app.command()
@click.argument("app_name")
@click.option(
    "--template",
    "-t",
    default="basic",
    show_default=True,
    help="Project template (see `unionml-tpu templates`).",
)
def init(app_name: str, template: str) -> None:
    """Initialize a unionml-tpu project from a template."""
    from unionml_tpu.templates import list_templates, render_template

    if template not in list_templates():
        raise click.BadParameter(f"unknown template {template!r}; available: {', '.join(list_templates())}")
    try:
        target = render_template(template, app_name, Path.cwd())
    except (ValueError, FileExistsError) as exc:
        raise click.ClickException(str(exc)) from exc
    click.echo(f"Created {target} from template {template!r}")


@app.command()
def templates() -> None:
    """List available project templates."""
    from unionml_tpu.templates import list_templates, template_description

    for name in list_templates():
        click.echo(f"{name:20s} {template_description(name)}")


@app.command()
@click.argument("app")
@click.option("--allow-uncommitted", is_flag=True, help="Deploy even with uncommitted changes.")
@click.option("--patch", is_flag=True, help="Code-only fast re-registration (no version bump of deps).")
@click.option("--schedule/--no-schedule", default=True, show_default=True, help="Deploy registered schedules.")
@click.option("--app-version", "-v", default=None, help="Explicit app version (default: git sha).")
def deploy(app: str, allow_uncommitted: bool, patch: bool, schedule: bool, app_version: Optional[str]) -> None:
    """Deploy a model app's workflows (and schedules) to the execution backend."""
    model = _load_model(app)
    version = model.remote_deploy(
        app_version=app_version, allow_uncommitted=allow_uncommitted, patch=patch, schedule=schedule
    )
    click.echo(f"Deployed app version {version}")


@app.command("activate-schedules")
@click.argument("app")
@click.option("--app-version", "-v", default=None)
@click.option("--name", "-n", "schedule_names", multiple=True, help="Schedule names (default: all).")
def activate_schedules(app: str, app_version: Optional[str], schedule_names) -> None:
    """Activate deployed schedules."""
    model = _load_model(app)
    model.remote_activate_schedules(app_version=app_version, schedule_names=list(schedule_names) or None)


@app.command("deactivate-schedules")
@click.argument("app")
@click.option("--app-version", "-v", default=None)
@click.option("--name", "-n", "schedule_names", multiple=True, help="Schedule names (default: all).")
def deactivate_schedules(app: str, app_version: Optional[str], schedule_names) -> None:
    """Deactivate deployed schedules."""
    model = _load_model(app)
    model.remote_deactivate_schedules(app_version=app_version, schedule_names=list(schedule_names) or None)


@app.command()
@click.argument("app")
@click.option("--inputs", "-i", default=None, help="JSON dict of training workflow inputs.")
@click.option("--app-version", "-v", default=None)
@click.option("--local", is_flag=True, help="Train locally in-process instead of on the backend.")
@click.option("--wait", "-w", is_flag=True, help="Wait for the remote execution to complete.")
@click.option("--profile-dir", default=None, help="Capture an xprof trace + stage timings into this directory (local mode).")
def train(
    app: str,
    inputs: Optional[str],
    app_version: Optional[str],
    local: bool,
    wait: bool,
    profile_dir: Optional[str],
) -> None:
    """Run a training job (remote by default, local with --local)."""
    model = _load_model(app)
    parsed = _parse_json_opt(inputs, "--inputs")
    if local:
        if profile_dir:
            from unionml_tpu.profiling import workflow_timings, xprof_trace

            with xprof_trace(profile_dir):
                _, metrics = model.train(**parsed)
            timings = workflow_timings(model.train_workflow())
            click.echo(json.dumps({"metrics": metrics, "stage_timings_s": timings}, default=str))
            return
        _, metrics = model.train(**parsed)
        click.echo(json.dumps({"metrics": metrics}, default=str))
        return
    result = model.remote_train(app_version=app_version, wait=wait, **parsed)
    if wait:
        click.echo(json.dumps({"metrics": result.metrics}, default=str))
    else:
        click.echo(f"Launched execution {result.id}")


@app.command()
@click.argument("app")
@click.option("--inputs", "-i", default=None, help="JSON dict of reader inputs.")
@click.option("--features", "-f", default=None, type=click.Path(exists=True, path_type=Path), help="JSON feature file.")
@click.option("--app-version", "-v", default=None)
@click.option("--model-version", "-m", default=None)
@click.option("--local", is_flag=True, help="Predict locally (requires a trained/loaded artifact or --model-path).")
@click.option("--model-path", default=None, type=click.Path(exists=True, path_type=Path), help="Local model file for --local.")
@click.option("--wait", "-w", is_flag=True)
def predict(
    app: str,
    inputs: Optional[str],
    features: Optional[Path],
    app_version: Optional[str],
    model_version: Optional[str],
    local: bool,
    model_path: Optional[Path],
    wait: bool,
) -> None:
    """Generate predictions from reader inputs or raw features."""
    model = _load_model(app)
    parsed_inputs = _parse_json_opt(inputs, "--inputs")
    feature_payload = None
    if features is not None:
        feature_payload = json.loads(Path(features).read_text())

    if local:
        if model_path is not None:
            model.load(model_path)
        predictions = model.predict(features=feature_payload, **parsed_inputs)
    else:
        result = model.remote_predict(
            app_version=app_version,
            model_version=model_version,
            wait=wait,
            features=feature_payload,
            **parsed_inputs,
        )
        if not wait:
            click.echo(f"Launched execution {result.id}")
            return
        predictions = result
    from unionml_tpu.serving import jsonable

    click.echo(json.dumps(jsonable(predictions), default=str))


@app.command("list-model-versions")
@click.argument("app")
@click.option("--app-version", "-v", default=None)
@click.option("--limit", default=10, show_default=True)
def list_model_versions(app: str, app_version: Optional[str], limit: int) -> None:
    """List model versions (training execution ids), newest first."""
    model = _load_model(app)
    for version in model.remote_list_model_versions(app_version=app_version, limit=limit):
        click.echo(version)


@app.command("list-prediction-ids")
@click.argument("app")
@click.option("--app-version", "-v", default=None)
@click.option("--limit", default=10, show_default=True)
def list_prediction_ids(app: str, app_version: Optional[str], limit: int) -> None:
    """List batch prediction ids, newest first."""
    model = _load_model(app)
    for pid in model.remote_list_prediction_ids(app_version=app_version, limit=limit):
        click.echo(pid)


@app.command("list-scheduled-training-runs")
@click.argument("app")
@click.argument("schedule_name")
@click.option("--app-version", "-v", default=None)
@click.option("--limit", default=5, show_default=True)
def list_scheduled_training_runs(app: str, schedule_name: str, app_version: Optional[str], limit: int) -> None:
    model = _load_model(app)
    for execution in model.remote_list_scheduled_training_runs(schedule_name, app_version=app_version, limit=limit):
        click.echo(f"{execution.id}\t{execution.status}")


@app.command("list-scheduled-prediction-runs")
@click.argument("app")
@click.argument("schedule_name")
@click.option("--app-version", "-v", default=None)
@click.option("--limit", default=5, show_default=True)
def list_scheduled_prediction_runs(app: str, schedule_name: str, app_version: Optional[str], limit: int) -> None:
    model = _load_model(app)
    for execution in model.remote_list_scheduled_prediction_runs(schedule_name, app_version=app_version, limit=limit):
        click.echo(f"{execution.id}\t{execution.status}")


@app.command("fetch-model")
@click.argument("app")
@click.option("--app-version", "-v", default=None)
@click.option("--model-version", "-m", default="latest", show_default=True)
@click.option("--output-file", "-o", required=True, type=click.Path(path_type=Path))
@click.option("--kwargs", default=None, help="JSON kwargs forwarded to model.save.")
def fetch_model(app: str, app_version: Optional[str], model_version: str, output_file: Path, kwargs: Optional[str]) -> None:
    """Fetch a trained model from backend lineage and save it locally."""
    from unionml_tpu.remote import get_model_artifact

    model = _load_model(app)
    model.artifact = get_model_artifact(model, app_version=app_version, model_version=model_version)
    model.save(output_file, **_parse_json_opt(kwargs, "--kwargs"))
    click.echo(f"Saved model to {output_file}")


@app.command("fetch-predictions")
@click.argument("app")
@click.option("--app-version", "-v", default=None)
@click.option("--prediction-id", "-p", default="latest", show_default=True)
@click.option("--output-file", "-o", required=True, type=click.Path(path_type=Path))
def fetch_predictions(app: str, app_version: Optional[str], prediction_id: str, output_file: Path) -> None:
    """Fetch batch predictions from backend lineage and write them as JSON."""
    model = _load_model(app)
    backend = model._remote
    if prediction_id == "latest":
        ids = model.remote_list_prediction_ids(app_version=app_version, limit=1)
        if not ids:
            raise click.ClickException("No predictions found.")
        prediction_id = ids[0]
    execution = backend.get_execution(prediction_id)
    predictions = model.remote_fetch_predictions(execution)
    Path(output_file).write_text(json.dumps(predictions, default=str))
    click.echo(f"Saved predictions to {output_file}")


@app.command()
@click.argument("app")
@click.option("--model-path", default=None, type=click.Path(exists=True, path_type=Path))
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", default=8000, show_default=True)
@click.option("--remote", is_flag=True, help="Load the model from backend lineage instead of a file.")
@click.option("--app-version", "-v", default=None)
@click.option("--model-version", "-m", default="latest", show_default=True)
@click.option(
    "--replicas",
    default=1,
    show_default=True,
    help="Generation engine replicas behind the fleet router (requires the "
    "app to define a generator factory; >1 enables /generate session "
    "routing and failover).",
)
@click.option(
    "--telemetry/--no-telemetry",
    "telemetry",
    default=True,
    show_default=True,
    help="Per-request span tracing + the Prometheus /metrics, "
    "/trace/{request_id}, and /traces/recent endpoints on the generation "
    "path (off: the request path pays one host branch per hook and "
    "nothing else).",
)
@click.option(
    "--trace-journal",
    default=None,
    type=click.Path(path_type=Path),
    help="Append completed request traces to this JSONL file (schema v1; "
    "the replay-simulator input). Implies --telemetry.",
)
def serve(
    app: str,
    model_path: Optional[Path],
    host: str,
    port: int,
    remote: bool,
    app_version: Optional[str],
    model_version: str,
    replicas: int,
    telemetry: bool,
    trace_journal: Optional[Path],
) -> None:
    """Serve the model over HTTP with a resident compiled predictor."""
    if model_path is not None:
        os.environ["UNIONML_MODEL_PATH"] = str(model_path)
    if replicas < 1:
        raise click.BadParameter("--replicas must be >= 1")
    from unionml_tpu.utils import configure_compile_cache

    configure_compile_cache()  # before the app module loads: it may compile at import
    model = _load_model(app)
    from unionml_tpu.serving import run_app, serving_app

    serving_kwargs = {}
    if replicas > 1:
        serving_kwargs["generate_replicas"] = replicas
    if trace_journal is not None:
        telemetry = True
        serving_kwargs["generate_trace_journal"] = str(trace_journal)
    serving_kwargs["generate_telemetry"] = telemetry
    http_app = serving_app(
        model, remote=remote, app_version=app_version, model_version=model_version,
        **serving_kwargs,
    )
    logger.info("Serving %s on %s:%d (replicas=%d)", app, host, port, replicas)
    run_app(http_app, host=host, port=port)


@app.command()
@click.argument("app", required=False)
@click.option("--poll-interval", default=10.0, show_default=True, help="Seconds between schedule evaluations.")
def scheduler(app: Optional[str], poll_interval: float) -> None:
    """Run the schedule executor loop (fires active cron / fixed-rate jobs)."""
    from unionml_tpu.backend import Scheduler, backend_from_config

    backend = _load_model(app)._remote if app else backend_from_config()
    runner = Scheduler(backend, poll_interval=poll_interval)
    click.echo("Scheduler running; Ctrl-C to stop.")
    try:
        runner.start()
        runner._thread.join()
    except KeyboardInterrupt:
        runner.stop()


def main() -> None:
    app(prog_name="unionml-tpu")


if __name__ == "__main__":
    main()
