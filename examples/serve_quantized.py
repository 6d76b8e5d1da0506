"""Serving with GSE-SEM-quantized weights: one stored copy, pick your
precision per request class (the paper's storage/compute decoupling).

  PYTHONPATH=src python examples/serve_quantized.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.models import stepfns, transformer as T
from repro.quant import gse_tensor as Q


def main():
    cfg = configs.get_config("qwen3_4b", smoke=True)
    params, _ = T.init_params(cfg, jax.random.key(0))
    packed = Q.quantize_tree(params, k=8, min_size=2048)

    B, P, GEN = 4, 10, 6
    prompts = jax.random.randint(jax.random.key(1), (B, P), 0,
                                 cfg.vocab_size)

    def generate(p):
        state = T.decode_state_init(cfg, B, max_len=P + GEN)
        serve = jax.jit(stepfns.make_serve_step(cfg))
        tok = prompts[:, 0]
        outs = []
        for pos in range(P + GEN - 1):
            nxt, state = serve(p, state, tok, jnp.asarray(pos, jnp.int32))
            tok = prompts[:, pos + 1] if pos + 1 < P else nxt
            if pos >= P - 1:
                outs.append(np.asarray(nxt))
        return np.stack(outs, 1)

    ref = generate(params)
    print(f"{'precision':12s} {'weight MB':>10s} {'tokens match ref':>18s}")
    for tag in (3, 2, 1):
        served = Q.dequantize_tree(packed, tag=tag, dtype=jnp.float32)
        out = generate(served)
        match = (out == ref).mean()
        mb = Q.tree_bytes(packed, tag) / 1e6
        print(f"gse tag {tag:4d} {mb:10.2f} {match:17.0%}")
    bf16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        if x.dtype == jnp.float32 else x, params)
    out = generate(bf16)
    mb = sum(x.size * 2 for x in jax.tree.leaves(params)) / 1e6
    print(f"{'bf16':12s} {mb:10.2f} {(out == ref).mean():17.0%}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
