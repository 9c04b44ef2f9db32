"""Generate the per-symbol API reference + CLI reference into docs/api/.

Reference parity: the reference ships sphinx autosummary pages
(``/root/reference/docs/source/api_reference.rst:1-12``,
``cli_reference.rst:1``). Here the generator is hand-rolled (no sphinx in the
image): every public symbol of the covered modules gets an entry rendered from
its signature + docstring, and the CLI page is rendered from click's own
``--help`` output, so docs can never drift from code — a CI test regenerates
and diffs (``tests/docs/test_api_reference.py``).

Usage: ``python tools/gen_api_docs.py [output_dir]`` (default ``docs/api``).
"""

import importlib
import inspect
import io
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

#: module path -> page title; every name in each module's __all__ is documented
MODULES = [
    ("unionml_tpu", "Top-level API"),
    ("unionml_tpu.dataset", "Dataset"),
    ("unionml_tpu.model", "Model"),
    ("unionml_tpu.schedule", "Schedules"),
    ("unionml_tpu.remote", "Remote backend client"),
    ("unionml_tpu.checkpoint", "Checkpointing"),
    ("unionml_tpu.models", "Model zoo"),
    ("unionml_tpu.parallel", "Parallelism"),
    ("unionml_tpu.serving", "Serving"),
    ("unionml_tpu.serving.scheduler", "SLO request scheduler"),
    ("unionml_tpu.serving.faults", "Fault injection & failure taxonomy"),
    ("unionml_tpu.serving.supervisor", "Engine supervision & recovery"),
    ("unionml_tpu.serving.fleet", "Fleet serving tier"),
    ("unionml_tpu.serving.telemetry", "Serving telemetry (traces & journal)"),
    ("unionml_tpu.serving.metrics", "Metrics registry & Prometheus exposition"),
    ("unionml_tpu.serving.slo", "SLO objectives, attainment & burn rate"),
    ("unionml_tpu.sim", "Fleet simulator (replay, synthetic traces, autoscaler)"),
    ("unionml_tpu.ops.attention", "Attention ops"),
    ("unionml_tpu.ops.paged_attention", "Paged attention (fused decode kernel)"),
    ("unionml_tpu.ops.ssm", "State-space ops (selective scan, decode step, causal convolution)"),
    ("unionml_tpu.ops.sampling", "Sampling ops"),
    ("unionml_tpu.ops.quant", "Quantization ops"),
    ("unionml_tpu.stage", "Staged execution"),
    ("unionml_tpu.defaults", "Resources & defaults"),
    ("unionml_tpu.debug", "Debugging"),
    ("unionml_tpu.profiling", "Profiling"),
    ("unionml_tpu.analysis", "Static analysis (graftlint)"),
    ("unionml_tpu.analysis.threads", "Thread-role inference (graftlint v4)"),
    ("unionml_tpu.analysis.rules_races", "Data-race & lock-contract rules (graftlint v4)"),
]


#: registries whose content at render time depends on what the process imported
#: before (the lint rules register themselves when a lint first runs): a page
#: that printed them differed from one pytest worker to the next
RUNTIME_REGISTRIES = {("unionml_tpu.analysis", "RULES")}


def _public_names(mod) -> list:
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [
        n
        for n, obj in vars(mod).items()
        if not n.startswith("_")
        and (inspect.isfunction(obj) or inspect.isclass(obj))
        and getattr(obj, "__module__", "").startswith(mod.__name__)
    ]


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default-value reprs can embed process-specific addresses ("<...object at
    # 0x7f...>"); scrub them so generation is deterministic (CI diffs the output)
    import re

    return re.sub(r" at 0x[0-9a-fA-F]+", "", sig)


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return doc if doc else "*(undocumented)*"


def _class_entry(name: str, cls, out: io.StringIO) -> None:
    out.write(f"### `{name}{_signature(cls)}`\n\n{_doc(cls)}\n\n")
    methods = []
    for mname, member in inspect.getmembers(cls):
        if mname.startswith("_") or not callable(member):
            continue
        # only methods defined by this class itself — inherited flax/optax surface
        # would bury the framework's own API under upstream docstrings
        if mname in vars(cls) and (inspect.isfunction(member) or inspect.ismethod(member)):
            methods.append((mname, member))
    for mname, member in methods:
        out.write(f"#### `{name}.{mname}{_signature(member)}`\n\n{_doc(member)}\n\n")


def render_module(module_path: str, title: str) -> str:
    mod = importlib.import_module(module_path)
    out = io.StringIO()
    out.write(f"# {title} (`{module_path}`)\n\n")
    head = inspect.getdoc(mod)
    if head:
        out.write(head + "\n\n")
    for name in _public_names(mod):
        obj = getattr(mod, name)
        if inspect.isclass(obj):
            _class_entry(name, obj, out)
        elif callable(obj):
            out.write(f"### `{name}{_signature(obj)}`\n\n{_doc(obj)}\n\n")
        elif (module_path, name) in RUNTIME_REGISTRIES:
            out.write(
                f"### `{name}`\n\nA module-level `{type(obj).__name__}` that fills as its modules "
                "load (whatever has registered itself); its content is not rendered.\n\n"
            )
        else:
            out.write(f"### `{name}`\n\n`{name} = {obj!r}`\n\n")
    return out.getvalue()


def render_cli() -> str:
    from click.testing import CliRunner

    from unionml_tpu.cli import app

    runner = CliRunner()
    out = io.StringIO()
    out.write("# CLI reference (`unionml-tpu`)\n\n")
    top = runner.invoke(app, ["--help"], prog_name="unionml-tpu")
    out.write("```\n" + top.output + "```\n\n")
    for cmd in sorted(app.commands):
        result = runner.invoke(app, [cmd, "--help"], prog_name="unionml-tpu")
        out.write(f"## `unionml-tpu {cmd}`\n\n```\n" + result.output + "```\n\n")
    return out.getvalue()


def generate(output_dir: Path) -> dict:
    """Render all pages; returns {relative_filename: content}."""
    pages = {}
    index = io.StringIO()
    index.write("# API reference\n\nGenerated by `tools/gen_api_docs.py` — do not edit by hand.\n\n")
    for module_path, title in MODULES:
        fname = module_path.replace(".", "_") + ".md"
        pages[fname] = render_module(module_path, title)
        index.write(f"- [{title}]({fname}) — `{module_path}`\n")
    pages["cli.md"] = render_cli()
    index.write("- [CLI reference](cli.md) — `unionml-tpu`\n")
    pages["index.md"] = index.getvalue()

    output_dir.mkdir(parents=True, exist_ok=True)
    for fname, content in pages.items():
        (output_dir / fname).write_text(content)
    return pages


if __name__ == "__main__":
    target = Path(sys.argv[1]) if len(sys.argv) > 1 else REPO_ROOT / "docs" / "api"
    pages = generate(target)
    print(f"wrote {len(pages)} pages to {target}")
