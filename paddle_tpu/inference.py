"""Inference predictor API (reference: paddle/fluid/inference/api/
analysis_predictor.cc:?, api/paddle_inference_api.h — AnalysisConfig +
AnalysisPredictor + create_paddle_predictor).

TPU-native design: the saved inference model (pruned Program + params,
io.save_inference_model) is loaded once into a private Scope; each
``run`` compiles the whole pruned block to one XLA executable per feed
signature (the Executor's compile cache replaces the reference's IR pass
manager + per-op execution), with optional bf16 inference in place of the
reference's TensorRT/int8 engines.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from paddle_tpu import io as _io
from paddle_tpu.executor import Executor, Scope, scope_guard
from paddle_tpu.framework import CPUPlace


class Config:
    """Predictor configuration (reference: AnalysisConfig)."""

    def __init__(self, model_dir: str,
                 model_filename: Optional[str] = None,
                 params_filename: Optional[str] = None):
        self.model_dir = model_dir
        self.model_filename = model_filename
        self.params_filename = params_filename
        self._use_tpu = True
        self._use_bf16 = False
        self._batch_buckets: tuple = ()

    def disable_tpu(self):
        self._use_tpu = False
        return self

    def enable_bf16(self):
        """bf16 inference (the TPU analog of the reference's fp16/TensorRT
        precision modes, contrib/float16 + inference/tensorrt)."""
        self._use_bf16 = True
        return self

    def set_batch_buckets(self, sizes):
        """Serve variable-size request batches through a FIXED set of
        compiled batch shapes: ``run`` pads each batch up to the nearest
        bucket (chunking by the largest when it overflows), so the
        executor compiles at most ``len(sizes)`` executables instead of
        one per observed batch size (the reference predictor's dynamic
        batching, without per-shape TRT engine rebuilds)."""
        sizes = sorted({int(s) for s in sizes})
        if not sizes or sizes[0] < 1:
            raise ValueError(f"batch buckets must be positive: {sizes}")
        self._batch_buckets = tuple(sizes)
        return self


class Predictor:
    """Compiled-program predictor (reference: AnalysisPredictor::Run)."""

    def __init__(self, config: Config):
        self._config = config
        self._closed = False
        self.scope = Scope()
        # default: jax's default device, whatever it is; disable_tpu()
        # asks for the CPU backend by name (Executor refuses a place the
        # process cannot honor)
        self._exe = Executor(None if config._use_tpu else CPUPlace())
        with scope_guard(self.scope):
            if os.path.exists(os.path.join(config.model_dir,
                                           "__params_int8__.npz")):
                # int8 PTQ artifact (slim.calibration
                # save_int8_inference_model): quantizable-op weights
                # dequantize from the int8 snapshot, everything else
                # (BN stats, biases) loads fp32; the frozen program
                # carries the static-scale QDQ ops, so serving numerics
                # match int8 deployment through the same Predictor/C-ABI
                # surface as float artifacts.
                from paddle_tpu.slim.calibration import (
                    load_int8_inference_model,
                )

                self.program, self._feed_names, self._fetch_vars = (
                    load_int8_inference_model(
                        config.model_dir, self._exe, scope=self.scope)
                )
            else:
                self.program, self._feed_names, self._fetch_vars = (
                    _io.load_inference_model(
                        config.model_dir,
                        self._exe,
                        model_filename=config.model_filename,
                        params_filename=config.params_filename,
                    )
                )
        if config._use_bf16:
            self.program._amp = True

    # --- reference-parity surface ---

    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return [v.name for v in self._fetch_vars]

    def _as_feed(self, inputs) -> Dict[str, np.ndarray]:
        if isinstance(inputs, dict):
            feed = dict(inputs)
            missing = [n for n in self._feed_names if n not in feed]
            if missing:
                raise KeyError(f"missing inputs: {missing}")
            return feed
        if len(inputs) != len(self._feed_names):
            raise ValueError(
                f"expected {len(self._feed_names)} inputs "
                f"({self._feed_names}), got {len(inputs)}"
            )
        return dict(zip(self._feed_names, inputs))

    def run(
        self,
        inputs: Union[Sequence[np.ndarray], Dict[str, np.ndarray]],
    ) -> List[np.ndarray]:
        """Positional (aligned with get_input_names) or name-keyed feeds
        -> list of output arrays. Compiled executables are cached per
        feed signature; parameters stay device-resident in the
        predictor's private scope and round-trip through each call via
        buffer donation (XLA aliases the unchanged buffers, so no copy).
        With ``Config.set_batch_buckets`` the batch dim is padded to the
        nearest bucket first, so the executable set stays at the bucket
        count whatever batch sizes arrive."""
        feed = self._as_feed(inputs)
        if self._config._batch_buckets:
            return self._run_bucketed(feed)
        return self._run_exact(feed)

    def _run_exact(self, feed: Dict[str, np.ndarray]) -> List[np.ndarray]:
        if self._closed:
            raise RuntimeError("Predictor.run after close()")
        with scope_guard(self.scope):
            return self._exe.run(
                self.program, feed=feed, fetch_list=self._fetch_vars
            )

    def _run_bucketed(self, feed: Dict[str, np.ndarray]) -> List[np.ndarray]:
        """Pad each chunk's batch dim up to a bucket shape and trim the
        padding back off the (batch-major) outputs."""
        buckets = self._config._batch_buckets

        def pick(remaining: int):
            take = min(remaining, buckets[-1])
            return take, next(s for s in buckets if s >= take)

        return self._run_padded_chunks(feed, pick)

    def _run_padded_chunks(self, feed, pick) -> List[np.ndarray]:
        """Shared fixed-signature batching core (run_batch and the
        bucketed run): split the batch into chunks sized by
        ``pick(remaining) -> (take, padded_size)``, zero-pad each chunk
        to its padded size, run, validate every fetch is batch-major
        over that size, trim the padding, and concatenate."""
        n = int(np.shape(next(iter(feed.values())))[0])
        if n == 0:
            raise ValueError("run got an empty (0-row) batch")
        for k, v in feed.items():
            if np.shape(v)[0] != n:
                raise ValueError(
                    f"input '{k}' batch {np.shape(v)[0]} != {n}")
        outs: List[List[np.ndarray]] = []
        lo = 0
        while lo < n:
            take, b = pick(n - lo)
            chunk = {k: np.asarray(v)[lo:lo + take]
                     for k, v in feed.items()}
            if take < b:
                chunk = {
                    k: np.concatenate(
                        [v, np.zeros((b - take,) + v.shape[1:], v.dtype)])
                    for k, v in chunk.items()
                }
            res = [np.asarray(r) for r in self._run_exact(chunk)]
            for i, r in enumerate(res):
                if r.ndim == 0 or r.shape[0] != b:
                    raise ValueError(
                        f"fetch #{i} has shape {r.shape}, not "
                        f"batch-major over batch {b}; batch-aggregated "
                        f"or scalar outputs cannot be re-chunked — "
                        f"fetch them via an exact-shape run() instead")
            outs.append([r[:take] for r in res])
            lo += take
        if len(outs) == 1:
            return outs[0]
        return [np.concatenate([o[i] for o in outs])
                for i in range(len(self._fetch_vars))]

    def warmup(self, inputs=None, shapes: Optional[Dict[str, tuple]] = None,
               dtypes: Optional[Dict[str, str]] = None):
        """Pre-compile (and prime the device) for a feed signature before
        serving traffic — the analog of the reference's warmup passes
        (AnalysisConfig warmup data for int8/TRT). Pass real sample
        ``inputs``, or ``shapes`` (+ optional ``dtypes``, default
        float32) to warm with zeros. Returns self."""
        if inputs is None:
            if not shapes:
                raise ValueError("warmup needs inputs or shapes")
            inputs = {
                n: np.zeros(shapes[n], np.dtype((dtypes or {}).get(
                    n, "float32")))
                for n in self._feed_names
            }
        self.run(inputs)
        return self

    def run_batch(
        self,
        inputs: Union[Sequence[np.ndarray], Dict[str, np.ndarray]],
        max_batch_size: int = 32,
    ) -> List[np.ndarray]:
        """Serve an arbitrary-size batch through FIXED-signature
        executables: the batch is split into ``max_batch_size`` chunks,
        the tail zero-padded to the chunk size, and results concatenated
        with the padding dropped. One compiled program serves every
        request size — the static-shape answer to the reference
        predictor's dynamic batching (no recompiles in steady state)."""
        feed = self._as_feed(inputs)
        return self._run_padded_chunks(
            feed, lambda remaining: (min(remaining, max_batch_size),
                                     max_batch_size))


    def serving_engine(self, cfg, *, supervised: bool = True, **kwargs):
        """Open a continuous-batching serving engine over this
        predictor's weights (serving.py; the reference parity point is
        AnalysisPredictor as a LONG-LIVED self-healing server process).
        ``supervised=True`` (default) wraps it in an EngineSupervisor —
        decode-loop thread, wedge watchdog, restart with replay; pass
        False for a caller-driven ServingEngine. ``cfg`` is the transformer config; ``kwargs``
        are the engine geometry/SLO knobs (slots, src_len, ...)."""
        from paddle_tpu import serving as _serving

        return _serving.serve(cfg, self, supervised=supervised, **kwargs)

    def close(self):
        """Release the predictor's compiled entries + staged feeds
        (mirroring ``Executor.close`` scoped to this predictor's private
        Scope) and drop its device-resident parameters. Idempotent; a
        ``run`` after close raises. The reference parity point is
        AnalysisPredictor's destructor releasing its per-predictor
        scope/engine state."""
        if self._closed:
            return
        self._closed = True
        self._exe.release_scope(self.scope)
        self.scope.clear()


def create_predictor(config: Config) -> Predictor:
    """reference: create_paddle_predictor<AnalysisConfig>."""
    return Predictor(config)
