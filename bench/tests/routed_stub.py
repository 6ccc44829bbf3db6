"""A served module as a later ``model_config`` PR's would look to the compare,
made of ``models/llama.py`` and its routed block: what the tests and
``tools/flip_rate.py`` hand to ``harness/compare.py`` in the program's place.

``module()`` gives ``init_params`` / ``quantize_params`` / ``prefill`` /
``decode`` with ``return_choice``: a spy in ``_moe_mlp``'s place repeats the
router's three operations (XLA merges them with the program's own), keeps
each block's chosen experts and calls the block itself, so the logits are the
program's, bit for bit.  Its switches make it another module:

``fault``: ``swap_near_ties`` uses and reports the (k+1)-th expert for the
k-th wherever the two router logits lie within ``eps`` standard deviations (a
sound computation in another precision); ``k_plus_8`` the (k+8)-th for the
k-th everywhere; ``no_renorm`` leaves the shares as the softmax gave them;
``misreport`` computes soundly and reports the (k+1)-th for the k-th.
``held``: the first ``held`` experts' weights are here; the router keeps its
width and the others' part of the result is left out.
``one_array``: ``init_cache`` gives one array a layer, K and V side by side on
the last axis, which ``prefill`` / ``decode`` split and join again.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.models import llama


def _block(inner, chosen, fault, held, eps):
    """``_moe_mlp`` with the choice kept in ``chosen``."""

    def moe(layer, x, cfg):
        k, E = cfg.num_experts_per_tok, cfg.num_experts
        logits = jnp.dot(x, layer["gate"], preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        if fault is None and held is None:
            chosen.append(jax.lax.top_k(probs, k)[1])
            return inner(layer, x, cfg)
        vals, idx = jax.lax.top_k(probs, k + 8)
        other = {"swap_near_ties": k, "misreport": k, "k_plus_8": k + 7}.get(fault)
        swapped = idx[:, :k]
        if other is not None:
            near = jnp.ones(x.shape[0], bool)
            if fault == "swap_near_ties":
                at = jnp.take_along_axis(logits, idx[:, k - 1:k + 1], -1)
                near = at[:, 0] - at[:, 1] < eps * jnp.std(logits, -1)
            swapped = swapped.at[:, k - 1].set(
                jnp.where(near, idx[:, other], idx[:, k - 1]))
        chosen.append(swapped)
        used = idx[:, :k] if fault == "misreport" else swapped
        share = jnp.take_along_axis(probs, used, -1)
        if fault != "no_renorm":
            share = share / share.sum(-1, keepdims=True)
        # The block as ``_moe_mlp`` computes it, over the experts held.
        n = E if held is None else held
        weights = jnp.sum(jax.nn.one_hot(used, E, dtype=jnp.float32)
                          * share[..., None], axis=1)[:, :n]
        gate = jnp.einsum("th,ehi->tei", x, layer["experts_gate"],
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("th,ehi->tei", x, layer["experts_up"],
                        preferred_element_type=jnp.float32)
        down = jnp.einsum(
            "tei,eih->teh", (llama._act(gate, cfg) * up).astype(x.dtype),
            layer["experts_down"], preferred_element_type=jnp.float32)
        return jnp.einsum("te,teh->th", weights, down).astype(x.dtype)

    return moe


def module(fault=None, held=None, one_array=False, eps=0.05):
    mod = types.ModuleType("routed_stub")

    def init_params(cfg, key, shardings=None):
        params = llama.init_params(cfg, key, shardings)
        for layer in params["layers"] if held is not None else ():
            for name in ("experts_gate", "experts_up", "experts_down"):
                layer[name] = layer[name][:held]
        return params

    def split(cache):
        if not one_array:
            return cache
        half = cache[0].shape[-1] // 2
        return [(c[..., :half], c[..., half:]) for c in cache]

    def join(cache):
        return ([jnp.concatenate(pair, -1) for pair in cache] if one_array
                else cache)

    def call(step, args, cache, mesh, return_choice):
        if fault is None and held is None and not return_choice:
            logits, cache = step(*args, split(cache), mesh=mesh)
            return logits, join(cache)
        chosen, inner = [], llama._moe_mlp
        llama._moe_mlp = _block(inner, chosen, fault, held, eps)
        try:
            logits, cache = step(*args, split(cache), mesh=mesh)
        finally:
            llama._moe_mlp = inner
        if return_choice:
            return logits, join(cache), jnp.stack(chosen)
        return logits, join(cache)

    def prefill(params, cfg, tokens, cached_len, prefix_block_ids,
                new_block_ids, valid_len, kv_caches, mesh=None,
                return_choice=False):
        return call(llama.prefill, (params, cfg, tokens, cached_len,
                                    prefix_block_ids, new_block_ids, valid_len),
                    kv_caches, mesh, return_choice)

    def decode(params, cfg, tokens, positions, block_tables, ctx_lens,
               slot_block_ids, slot_offsets, kv_caches, mesh=None,
               return_choice=False):
        return call(llama.decode, (params, cfg, tokens, positions,
                                   block_tables, ctx_lens, slot_block_ids,
                                   slot_offsets), kv_caches, mesh, return_choice)

    def init_cache(cfg, num_blocks, block_size, sharding):
        zeros = jax.jit(
            lambda: jnp.zeros((num_blocks, block_size, cfg.num_kv_heads,
                               2 * cfg.head_dim), cfg.dtype),
            out_shardings=sharding)
        return [zeros() for _ in range(cfg.num_layers)]

    mod.init_params, mod.quantize_params = init_params, llama.quantize_params
    mod.prefill, mod.decode = prefill, decode
    if one_array:
        mod.init_cache = init_cache
    return mod
