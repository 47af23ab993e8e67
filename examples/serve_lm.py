"""Batched serving example: prefill + decode across architecture families
(GQA dense, MoE+SWA ring cache, RWKV recurrent state, multi-codebook audio).

    PYTHONPATH=src python examples/serve_lm.py
"""
from repro.launch.serve import serve

for arch in ("qwen2-7b", "mixtral-8x22b", "rwkv6-3b", "musicgen-medium"):
    res = serve(arch, reduced=True, batch=2, prompt_len=32, gen=12)
    print(f"[{arch}] generated {res.tokens.shape} tokens: compile {res.compile_s:.2f}s, "
          f"prefill {res.prefill_s:.3f}s, {res.ms_per_token:.2f} ms/token")
