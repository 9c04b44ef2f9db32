"""FastAPI adapter: attach unionml-tpu serving endpoints to a user's FastAPI app.

Reference parity: ``unionml/fastapi.py:15-70`` — identical endpoint contract. Only
importable when ``fastapi`` is installed (optional dependency); the native aiohttp app
(:mod:`unionml_tpu.serving.app`) is the default serving surface.
"""

from http import HTTPStatus
from typing import Any, Dict, List, Optional, Union

from fastapi import Body, FastAPI, HTTPException
from fastapi.responses import HTMLResponse

from unionml_tpu._logging import logger
from unionml_tpu.serving.app import _INDEX_HTML, jsonable, load_model_artifact
from unionml_tpu.serving.resident import ResidentPredictor


def attach_fastapi(
    model: Any,
    app: FastAPI,
    remote: bool = False,
    app_version: Optional[str] = None,
    model_version: str = "latest",
    resident: bool = True,
    buckets: Optional[Any] = None,
    seq_buckets: Optional[Any] = None,
    example_features: Optional[Any] = None,
    mesh: Optional[Any] = None,
    param_specs: Optional[Any] = None,
    **unsupported: Any,
) -> FastAPI:
    from unionml_tpu.serving.resident import DEFAULT_BUCKETS

    if unsupported:
        # the aiohttp app supports more options (request coalescing); say so instead
        # of silently ignoring them on this path
        logger.warning(
            "attach_fastapi ignoring unsupported serving options: %s", sorted(unsupported)
        )

    predictor = (
        ResidentPredictor(
            model,
            buckets=buckets or DEFAULT_BUCKETS,
            seq_buckets=seq_buckets,
            example_features=example_features,
            # the mesh-sharded executor sits entirely below the endpoint
            # contract: /predict and /health behave identically above it
            mesh=mesh,
            param_specs=param_specs,
        )
        if resident
        else None
    )

    @app.on_event("startup")
    async def setup_model():
        load_model_artifact(model, remote=remote, app_version=app_version, model_version=model_version)
        if predictor is not None:
            # graftlint: disable=async-blocking -- startup hook: the warmup compile+sync runs before the server accepts any traffic, so blocking the (idle) loop here is the point
            predictor.setup()

    @app.get("/", response_class=HTMLResponse)
    def root():
        return _INDEX_HTML

    # SYNC on purpose (graftlint async-blocking true positive, fixed): the
    # compiled predictor call and its device fetch block for milliseconds+,
    # which on an ``async def`` endpoint stalls the event loop for every
    # in-flight request. FastAPI runs sync endpoints in its threadpool — same
    # contract, no loop stall (the aiohttp app routes through run_in_executor
    # for the same reason).
    @app.post("/predict")
    def predict(
        inputs: Optional[Union[Dict[str, Any], None]] = Body(None),
        features: Optional[List[Any]] = Body(None),
    ):
        if inputs is None and features is None:
            raise HTTPException(status_code=500, detail="inputs or features must be supplied.")
        # empty {} means reader-defaults ONLY when no features came along (matches app.py)
        if inputs is not None and (inputs or features is None):
            result = predictor.predict(**inputs) if predictor is not None else model.predict(**inputs)
        else:
            # model.predict runs the feature pipeline itself; don't pre-process here
            result = (
                predictor.predict(features=features)
                if predictor is not None
                else model.predict(features=features)
            )
        return jsonable(result)

    @app.get("/health")
    async def health():
        if model.artifact is None:
            raise HTTPException(status_code=500, detail="Model artifact not found.")
        return {"message": HTTPStatus.OK.phrase, "status": HTTPStatus.OK.value}

    return app
