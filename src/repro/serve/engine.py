"""Batched serving engine: prefill + greedy KV-cache decode.

Mirrors a production continuous-batching server in miniature: fixed batch
slots, one jitted prefill and one jitted decode step (both shardable with the
same specs the dry-run uses).  Both steps take the KV cache donated and
update it in place.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


class ServeEngine:
    """Continuous-batching inference engine over one ModelAPI.

    ``generate`` runs greedy decoding against the jitted prefill/decode
    steps; ``comm_profile`` exports the engine's measured communication
    footprint, which calibrates the cluster simulator's serving archetype
    (:mod:`repro.sim.serving` — per-request KV bytes moved from prefill
    to decode pods in a disaggregated deployment).
    """

    def __init__(self, api, params, batch: int, s_max: int, mesh=None):
        self.api = api
        self.params = params
        self.batch = batch
        self.s_max = s_max
        self.mesh = mesh
        # The cache (argument 2) is donated: the layer scan carries it and
        # writes only the new rows, so XLA aliases it to the returned cache
        # and neither step allocates a second one.
        self._prefill = jax.jit(api.prefill, donate_argnums=2)
        self._decode = jax.jit(api.decode, donate_argnums=2)
        self._batches = 0  # generate calls so far: the ``batch`` of its spans

    def compiled_steps(self, batch_inputs: Dict[str, Any]) -> Dict[str, Any]:
        """The prefill and decode steps compiled for ``batch_inputs`` (as
        ``generate`` takes them; arrays or shape structs), without running
        them.  Compiles anew: keep it out of a timed path."""
        inputs = {k: jax.ShapeDtypeStruct(np.shape(v), v.dtype) for k, v in batch_inputs.items()}
        B = inputs["tokens"].shape[0]
        cache = jax.eval_shape(lambda: self.api.init_cache(B, self.s_max))
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        return {
            "prefill": self._prefill.lower(self.params, inputs, cache).compile(),
            "decode": self._decode.lower(self.params, tok, cache).compile(),
        }

    def cache_alias_bytes(self, batch_inputs: Dict[str, Any]) -> Dict[str, int]:
        """Bytes each compiled step (``prefill``, ``decode``) aliases from
        its cache input to its output: the cache's own bytes when the step
        updates the cache in place, 0 when it copies it."""
        return {
            name: int(step.memory_analysis().alias_size_in_bytes)
            for name, step in self.compiled_steps(batch_inputs).items()
        }

    def comm_profile(self) -> Dict[str, float]:
        """Measured per-request communication profile of this engine.

        ``kv_bytes_per_token`` is derived from the *real* cache pytree —
        the byte growth of ``api.init_cache`` per context slot — so it is
        exact for every architecture family (GQA, MLA latents, hybrid
        patterns whose mamba/rwkv state does not grow with context), not
        a formula restated.  The analytic twin is
        :func:`repro.dist.demand.kv_bytes_per_token`;
        ``tests/test_serving.py`` pins the two against each other.  The
        simulator sizes prefill→decode KV migration flows
        (:func:`repro.dist.demand.kv_flow`) from this number.
        """
        def nbytes(s_max: int) -> int:
            cache = self.api.init_cache(1, s_max)
            return int(
                sum(x.nbytes for x in jax.tree_util.tree_leaves(cache))
            )
        s0, s1 = 8, 16
        per_token = (nbytes(s1) - nbytes(s0)) / (s1 - s0)
        cfg = self.api.cfg
        return {
            "kv_bytes_per_token": float(per_token),
            "fixed_state_bytes": float(nbytes(s0) - per_token * s0),
            "dtype_bytes": float(jnp.dtype(cfg.compute_dtype).itemsize),
            "num_layers": float(cfg.num_layers),
            "batch_slots": float(self.batch),
        }

    def generate(
        self, batch_inputs: Dict[str, np.ndarray], max_new_tokens: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Greedy generation.  batch_inputs must contain "tokens" (B, S0) and
        any modality extras the arch needs (frames/patches).

        Returns the new tokens (B, max_new_tokens) and the fp32 logits
        (B, V) of the last step, from which the last token was taken.

        Each phase runs in a host span of the profiler's trace
        (``serve.setup``, ``serve.prefill``, then ``serve.decode`` and
        ``serve.sample`` per new token after the first, ``serve.collect``),
        all carrying this call's ``batch`` number and the per-token ones the
        decode ``step`` (from 0).  With no profiler running a span costs one
        check."""
        self._batches += 1
        n = self._batches
        with TraceAnnotation("serve.setup", batch=n):
            B, S0 = batch_inputs["tokens"].shape
            cache = self.api.init_cache(B, self.s_max)
            batch_inputs = {k: jnp.asarray(v) for k, v in batch_inputs.items()}
        with TraceAnnotation("serve.prefill", batch=n):
            logits, cache = self._prefill(self.params, batch_inputs, cache)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out = [tok]
        for i in range(max_new_tokens - 1):
            with TraceAnnotation("serve.decode", batch=n, step=i):
                logits, cache = self._decode(self.params, tok[:, None], cache)
            with TraceAnnotation("serve.sample", batch=n, step=i):
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            out.append(tok)
        with TraceAnnotation("serve.collect", batch=n):
            tokens = np.stack([np.asarray(t) for t in out], axis=1)
            last = np.asarray(logits[:, -1])
        return tokens, last
