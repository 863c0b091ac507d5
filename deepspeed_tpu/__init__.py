"""deepspeed_tpu — a TPU-native training/inference framework with the
capabilities of DeepSpeed (reference: /root/reference, v0.15.5), built on
JAX/XLA/Pallas/pjit rather than torch/CUDA/NCCL.

Top-level API mirrors ``deepspeed/__init__.py``:
  - ``initialize(...)`` -> (engine, optimizer, dataloader, lr_scheduler)
  - ``init_inference(...)`` -> InferenceEngine
  - ``comm`` — collectives facade
  - ``zero`` — ZeRO sharding utilities
"""

import time as _time

_T_IMPORT = _time.perf_counter()    # imports run before telemetry can be on

__version__ = "0.1.0"
__git_branch__ = "main"

from . import comm  # noqa: F401
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .parallel.mesh import MeshTopology, TopologyConfig, get_topology, set_topology  # noqa: F401

# seconds this package's own imports took (jax included when this is the
# first import of it): the part of a run's set-up no span can cover
IMPORT_SECONDS = _time.perf_counter() - _T_IMPORT


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               mesh_param=None,
               config_params=None):
    """Initialize the engine (reference: deepspeed/__init__.py:69).

    `model` may be a deepspeed_tpu Model (models/base.py), a flax Module,
    or an (init_fn, apply_fn) pair. Returns a tuple of
    ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    try:
        from .runtime.engine import DeepSpeedEngine
        from .runtime.pipe.module import PipelineModule
    except ModuleNotFoundError as e:  # pragma: no cover
        raise NotImplementedError(
            f"deepspeed_tpu.initialize requires {e.name}, which is not built "
            "yet in this checkout") from e

    config = config if config is not None else config_params
    from .runtime.config import DeepSpeedConfig as _Cfg
    config = _Cfg.from_any(config)  # parsed once; constructors accept it
    if hasattr(model, "moe_serving_dispatch"):
        # belt-and-braces: init_inference binds the serving dispatch
        # flag to its own shallow copy and never mutates the shared
        # instance, but a user may have set the class/instance attr by
        # hand; training must use the capacity einsum (drops are a
        # training regularizer, and ep sharding needs the all-to-all)
        model.moe_serving_dispatch = False
    if isinstance(model, PipelineModule):
        from .runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(
            model=model, optimizer=optimizer, config=config,
            training_data=training_data, lr_scheduler=lr_scheduler,
            collate_fn=collate_fn, mpu=mpu or model.topology(), args=args)
    else:
        zc = config.zero_optimization
        stream = zc.offload_param.stream
        auto = stream is None
        if auto:
            import jax as _jax
            # auto only when the caller didn't hand us objects the
            # streamed engine can't take over (model_parameters ARE
            # consumable — the streamed engine loads them as the fp32
            # master instead of re-initializing from config.seed)
            stream = (zc.stage == 3 and zc.offload_param.device == "cpu"
                      and len(_jax.devices()) == 1
                      and optimizer is None and training_data is None
                      and mpu is None and mesh_param is None)
        if stream:
            # models larger than HBM on one chip: layer-streamed params
            # + optimizer through pinned_host (ZeRO-Infinity capability;
            # reference stage3.py:1926 + swap_tensor/)
            from .runtime.infinity import StreamedZeroEngine
            try:
                if mpu is not None or mesh_param is not None:
                    raise NotImplementedError(
                        "param streaming is single-chip; mpu/mesh_param "
                        "need the sharded engine")
                if optimizer is not None or training_data is not None:
                    raise NotImplementedError(
                        "param streaming owns its optimizer/data loop; "
                        "pass optimizer via config and feed batches to "
                        "train_batch directly")
                engine = StreamedZeroEngine(
                    model, config, lr_scheduler=lr_scheduler,
                    model_parameters=model_parameters)
                return engine, None, None, engine.lr_schedule
            except (NotImplementedError, ValueError):
                if not auto:
                    raise
                # auto mode: configs the streamed engine doesn't cover
                # (ga>1, fp16, non-Adam, non-DecoderLM, unconsumable
                # model_parameters) keep the sharded whole-tree-fetch
                # path that served them before
        engine_cls = DeepSpeedEngine
        if config.hybrid_engine.enabled:
            from .runtime.hybrid_engine import DeepSpeedHybridEngine
            engine_cls = DeepSpeedHybridEngine
        engine = engine_cls(
            args=args, model=model, optimizer=optimizer,
            model_parameters=model_parameters, training_data=training_data,
            lr_scheduler=lr_scheduler, mpu=mpu, config=config,
            collate_fn=collate_fn, mesh_param=mesh_param)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine (reference: deepspeed/__init__.py:291)."""
    try:
        from .inference.engine import InferenceEngine
        from .inference.config import DeepSpeedInferenceConfig
    except ModuleNotFoundError as e:  # pragma: no cover
        raise NotImplementedError(
            f"deepspeed_tpu.init_inference requires {e.name}, which is not "
            "built yet in this checkout") from e
    params = kwargs.pop("params", None)
    if isinstance(model, str):
        # HF checkpoint directory: load real pretrained weights
        # (reference: init_inference's checkpoint loading path,
        # inference/engine.py:326 + module_inject/load_checkpoint.py:21).
        # Caller-supplied params skip the weight read — only the
        # config.json translation is needed then.
        from .checkpoint.huggingface import HuggingFaceCheckpointEngine
        from .models import get_model_class
        hf_eng = HuggingFaceCheckpointEngine(model)
        cfg_m = hf_eng.model_config()
        model = get_model_class(hf_eng.family)(cfg_m)
        if params is None:
            params = hf_eng.load_params(cfg_m)
    cfg = DeepSpeedInferenceConfig.from_any(config, **kwargs)
    return InferenceEngine(model, cfg, params=params)


def add_config_arguments(parser):
    """argparse passthrough (reference: deepspeed/__init__.py:268)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for user code)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_hidden())
    return parser


def argparse_hidden():
    import argparse
    return argparse.SUPPRESS


def default_inference_config():
    from .inference.config import DeepSpeedInferenceConfig
    return DeepSpeedInferenceConfig().model_dump()
