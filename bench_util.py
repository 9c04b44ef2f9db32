"""Side-effect-free helper of ``bench_kernels.py``.

Deliberately free of module-level configuration: ``bench.py`` sets process-wide
logging levels at import, which ``bench_kernels.py`` must NOT inherit just
to reuse a path-policy function.
"""

import os


def resolve_artifact_path(out_path: str, backend: str) -> str:
    """Where a bench run may write its committed artifact.

    One policy: accelerator runs own the canonical
    artifact name; CPU smoke runs divert to a ``_cpu``-suffixed sibling
    (gitignored) so host timings can never overwrite a TPU measurement.
    """
    if backend != "cpu":
        return out_path
    base, ext = os.path.splitext(out_path)
    return f"{base}_cpu{ext}"
