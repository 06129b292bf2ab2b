"""Serve a Llama with weight-only int8 decode (half the weight stream —
decodes below the bf16 HBM floor on TPU)."""
import os
import sys

import numpy as np

# runnable from the repo root without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from paddle_tpu.models.llama import (greedy_generate, init_llama_params,
                                         llama_tiny, quantize_llama_int8)
    config = llama_tiny(vocab=512, hidden=64, layers=4, heads=4, kv_heads=4,
                        inter=128, seq=96)
    params = quantize_llama_int8(init_llama_params(config, seed=0))
    prompt = np.random.RandomState(0).randint(0, 512, (1, 8)).astype(np.int32)
    toks = greedy_generate(params, prompt, config, max_new_tokens=16)
    print("prompt:", prompt[0].tolist())
    print("continuation:", toks[0].tolist())


if __name__ == "__main__":
    main()
