"""The serving reference the engine tests compare against, with no engine
in it: each request alone, a batch-1 ``prefill`` into a fresh contiguous
cache, then ``decode_step`` one token at a time, sampled as the engine
samples (``sample_tokens`` keyed on (seed, rid, step)).  Alone, a request
has no neighbours and no padding, so its stream is the model's own
continuation.
"""
import jax
import jax.numpy as jnp

from repro.serve.sampling import sample_tokens


def serve_alone(model, params, cfg, prompts, max_new, *, temperature=0.0,
                seed=0, max_len=64, eos_id=1):
    """{rid: output tokens} for ``prompts``, ``rid`` its index, each served
    alone in a cache of ``max_len`` tokens."""
    prefill = jax.jit(model.prefill, static_argnums=2)
    decode = jax.jit(model.decode_step)
    out = {}
    for rid, prompt in enumerate(prompts):
        batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
        if cfg.family == "encdec":      # the engine's zero frames
            batch["frames"] = jnp.zeros(
                (1, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)
        logits, cache = prefill(params, batch, max_len)
        toks = out[rid] = []
        while True:
            tok = int(sample_tokens(logits, [(rid, len(toks))], seed=seed,
                                    temperature=temperature)[0])
            toks.append(tok)
            if tok == eos_id or len(toks) >= max_new:
                break
            pos = len(prompt) + len(toks) - 1
            logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32),
                                   cache, jnp.asarray([pos], jnp.int32))
    return out
