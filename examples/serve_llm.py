"""Token serving example: the family-adapter serving core driving a reduced
binary LM through TokenServeEngine — admission, cost attribution and span
tracing shared with the GNN engines, zero steady-state recompiles, and the
served streams asserted BITWISE equal to a direct ``decode_step`` loop.
Optionally with BitGNN bit-packed weights (32x smaller projections).

    PYTHONPATH=src python examples/serve_llm.py --requests 6 --quant
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.env import enable_compile_cache
from repro.models import transformer
from repro.quant.binary_linear import quantize_params, quantized_param_bytes
from repro.serve.token_engine import TokenServeEngine
from repro.serve.token_session import TokenStore


def direct_reference(cfg, params, prompt, max_new):
    """Ground truth: a python loop of jit(decode_step) with argmax feedback
    — exactly the program the serving path must reproduce bitwise."""
    step = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        p, cfg, c, t, pos))
    total = prompt.size + max_new
    cache = transformer.init_cache(
        cfg, 1, max(64, int(2 ** np.ceil(np.log2(total)))))
    out, prev = [], None
    for t in range(prompt.size + max_new - 1):
        tok = prompt[t] if t < prompt.size else prev
        logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32), t)
        prev = int(np.argmax(np.asarray(logits[0, 0, :cfg.vocab])))
        if t >= prompt.size - 1:
            out.append(prev)
    return np.asarray(out[:max_new], np.int32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--quant", action="store_true")
    args = ap.parse_args()

    cfg = reduced_config(get_config("stablelm-1.6b")).resolve_for_mesh(tp=1)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    fp_bytes = quantized_param_bytes(params)
    if args.quant:
        print(f"bitgnn quantized params: "
              f"{quantized_param_bytes(quantize_params(params))/1e6:.2f} MB "
              f"(fp: {fp_bytes/1e6:.2f} MB)")

    store = TokenStore(max_batch=4, max_len=128, chunk=8,
                       warm_len=12, warm_new=args.max_new)
    store.register_model("lm", cfg, params, quantize=args.quant)
    eng = TokenServeEngine(store)
    warm = eng.warmup("lm")
    c0 = eng.compile_count

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, rng.integers(3, 10)).astype(np.int32)
               for _ in range(args.requests)]
    queries = eng.submit_many("lm", prompts, max_new=args.max_new)
    eng.run_until_drained()
    eng.close()

    qparams = quantize_params(params) if args.quant else params
    for q, prompt in zip(queries, prompts):
        ref = direct_reference(cfg, qparams, prompt, args.max_new)
        assert np.array_equal(q.tokens, ref), \
            f"query {q.qid}: served stream diverged from decode_step loop"
        print(f"req {q.qid}: prompt[{prompt.size}] -> {q.tokens.tolist()} "
              f"(ttft {q.ttft_s*1e3:.1f} ms)")
    steady = eng.compile_count - c0
    print(f"served == direct decode_step loop for all {len(queries)} "
          f"requests; warmup compiles {warm}, steady-state compiles {steady}")


if __name__ == "__main__":
    main()
