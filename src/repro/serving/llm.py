"""``LLM`` — one-line construction of a (quantized) serving stack.

Wires the pieces the paper's system section assembles by hand —
``configs.registry`` (architecture resolution), ``checkpoint.checkpointer``
(weight restore), ``models.quantize`` (RTN / GPTQ int4 artifacts) and the
continuous-batching ``ServingEngine`` — behind a vLLM-shaped facade::

    from repro.serving import LLM, SamplingParams

    llm = LLM.load("qwen2-1.5b", quant="gptq-int4", reduced=True)
    outs = llm.generate(prompts, SamplingParams(top_k=40, stop=[eos]))
    for out in llm.stream(more_prompts, SamplingParams(temperature=0.8)):
        print(out.request_id, out.new_token_ids, out.finish_reason)

Prompts are token-id lists (the repo has no tokenizer); pass
``detokenizer=`` a ``List[int] -> str`` callable to get ``text`` fields.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.configs.base import ModelConfig, QuantConfig
from repro.configs.registry import get_config, get_reduced
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.params import RequestOutput, SamplingParams

QUANT_MODES = (None, "rtn-int4", "gptq-int4")

Prompt = Sequence[int]


def _synthetic_calib(cfg: ModelConfig, key, n_batches: int = 2,
                     batch: int = 2, seq: int = 32) -> List[dict]:
    """Random-token calibration batches for GPTQ when none are supplied
    (good enough for smoke-scale models; pass real data for quality)."""
    return [{"tokens": jax.random.randint(jax.random.fold_in(key, i),
                                          (batch, seq), 0, cfg.vocab_size)}
            for i in range(n_batches)]


class LLM:
    """Facade owning a config, (possibly quantized) params and an engine."""

    def __init__(self, cfg: ModelConfig, params, *,
                 detokenizer: Optional[Callable[[List[int]], str]] = None,
                 **engine_kw):
        self.cfg = cfg
        self.params = params
        self.engine = ServingEngine(cfg, params, detokenizer=detokenizer,
                                    **engine_kw)

    # ------------------------------------------------------------ builder
    @classmethod
    def load(cls, config_name: str, *, quant: Optional[str] = None,
             kv_cache_dtype: str = "bf16",
             checkpoint: Optional[str] = None, reduced: bool = False,
             overrides: Optional[dict] = None, seed: int = 0,
             quant_group_size: int = 32, calib_batches: Optional[list] = None,
             **engine_kw) -> "LLM":
        """Build a ready-to-serve ``LLM`` from a registry config name.

        quant:      None | "rtn-int4" (round-to-nearest int4 of every
                    matmul weight, any family) | "gptq-int4" (Hessian
                    OBQ over calibration data, dense-family models).
        kv_cache_dtype: "bf16" (dense pool, the parity oracle) | "int8"
                    (quantized paged KV pool: int8 values + per-block-
                    per-head f32 scales, ~2x lower KV bytes/token vs
                    bf16; greedy outputs match bf16 within quantization
                    tolerance — see docs/API.md).
        checkpoint: a ``checkpoint.Checkpointer`` directory; the latest
                    step's ``params`` tree replaces the random init
                    (quantization, if any, runs after the restore).
        reduced:    use the tiny same-family CPU config (tests/demos).
        overrides:  ``ModelConfig.replace`` fields applied after config
                    resolution (e.g. ``num_layers``, ``num_kv_heads``).
        seed:       param init (when no checkpoint) and the engine's
                    default per-request sampling streams.
        engine_kw:  forwarded to ``ServingEngine`` (max_slots,
                    num_blocks, max_blocks_per_seq,
                    max_num_batched_tokens, enable_chunked_prefill,
                    enable_async_step, prefill_bucket [whole-prompt
                    path only], rt, max_horizon, detokenizer via
                    __init__; robustness:
                    max_waiting, shed_policy, enable_guards,
                    fault_injector, max_dispatch_retries,
                    retry_backoff_s — see docs/API.md "Fault
                    tolerance"; observability: enable_telemetry,
                    trace_capacity, profile_labels — see
                    docs/OBSERVABILITY.md).
                    ``max_num_batched_tokens`` caps the tokens one
                    engine step may batch (decodes first, then prefill
                    chunks); ``enable_chunked_prefill=False`` restores
                    the stop-the-world whole-prompt prefill (the parity
                    oracle); ``max_horizon`` caps the tokens one
                    decode-only megastep dispatch yields (1 = one
                    dispatch per token);
                    ``enable_async_step=False`` restores the
                    read-back-every-step loop instead of the default
                    one-step-deferred async pipeline (see docs/PERF.md
                    "Async pipeline").
        """
        if quant not in QUANT_MODES:
            raise ValueError(f"unknown quant mode {quant!r}; "
                             f"expected one of {QUANT_MODES}")
        cfg = (get_reduced(config_name, **(overrides or {})) if reduced
               else get_config(config_name))
        if overrides and not reduced:
            cfg = cfg.replace(**overrides)
        key = jax.random.PRNGKey(seed)
        if checkpoint is not None:
            from repro.checkpoint.checkpointer import Checkpointer
            ckpt = Checkpointer(checkpoint)
            step = ckpt.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no step_* checkpoints under {checkpoint!r}")
            template = jax.eval_shape(lambda: T.init_params(cfg, key))
            restored, _extra = ckpt.restore(step, {"params": template})
            params = restored["params"]
        else:
            params = T.init_params(cfg, key)

        if quant == "rtn-int4":
            from repro.models.quantize import quantize_params_rtn
            params = quantize_params_rtn(params, cfg,
                                         group_size=quant_group_size)
        elif quant == "gptq-int4":
            from repro.models.quantize import gptq_quantize_model
            if cfg.family not in ("dense", "vlm", "audio"):
                raise ValueError(
                    f"gptq-int4 supports dense-family models, not "
                    f"{cfg.family!r} ({cfg.name}); use quant='rtn-int4'")
            calib = calib_batches or _synthetic_calib(
                cfg, jax.random.fold_in(key, 1))
            params = gptq_quantize_model(
                cfg, params, calib,
                QuantConfig(bits=4, group_size=quant_group_size))
        return cls(cfg, params, seed=seed, kv_cache_dtype=kv_cache_dtype,
                   **engine_kw)

    # ------------------------------------------------------------ serving
    @staticmethod
    def _as_prompt_list(prompts: Union[Prompt, Sequence[Prompt]]
                        ) -> List[List[int]]:
        if prompts and isinstance(prompts[0], (int, np.integer)):
            return [[int(t) for t in prompts]]   # a single prompt
        return [[int(t) for t in p] for p in prompts]

    def _submit(self, prompts, sampling_params) -> List[int]:
        plist = self._as_prompt_list(prompts)
        if sampling_params is None or isinstance(sampling_params,
                                                 SamplingParams):
            sps = [sampling_params] * len(plist)
        else:
            sps = list(sampling_params)
            if len(sps) != len(plist):
                raise ValueError(f"{len(plist)} prompts but "
                                 f"{len(sps)} sampling params")
        return [self.engine.add(p, sp) for p, sp in zip(plist, sps)]

    def generate(self, prompts: Union[Prompt, Sequence[Prompt]],
                 sampling_params: Union[SamplingParams,
                                        Sequence[SamplingParams],
                                        None] = None
                 ) -> List[RequestOutput]:
        """Run all prompts to completion; returns one finished
        ``RequestOutput`` per prompt, in submission order."""
        rids = self._submit(prompts, sampling_params)
        final = {}
        for out in self.engine.stream():
            if out.finished:
                final[out.request_id] = out
        missing = [r for r in rids if r not in final]
        if missing:
            raise RuntimeError(f"requests {missing} did not finish "
                               f"(engine stalled?)")
        return [final[r] for r in rids]

    def abort(self, request_id: int) -> bool:
        """Cancel a request by id (see ``ServingEngine.abort``): KV
        blocks and prefix-hash registrations are released immediately;
        the "aborted" finish event surfaces with the next engine step."""
        return self.engine.abort(request_id)

    def stream(self, prompts: Union[Prompt, Sequence[Prompt]],
               sampling_params: Union[SamplingParams,
                                      Sequence[SamplingParams],
                                      None] = None
               ) -> Iterator[RequestOutput]:
        """Submit prompts and yield ``RequestOutput`` deltas as horizons
        complete — first tokens arrive long before the batch drains. More
        prompts may be added concurrently via ``llm.engine.add``."""
        self._submit(prompts, sampling_params)
        yield from self.engine.stream()

    # ------------------------------------------------------------ shutdown
    def close(self) -> None:
        """Shut the engine down cleanly (flush the async pipeline, join
        the detokenize worker — see ``ServingEngine.close``).  Events
        still in flight are discarded here; drain with ``generate`` /
        ``stream`` first if they matter.  Idempotent; ``with LLM.load(
        ...) as llm:`` calls it automatically."""
        self.engine.close()

    def __enter__(self) -> "LLM":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
