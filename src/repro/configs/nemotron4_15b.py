"""Nemotron-4-15B — dense GQA, squared-ReLU, 256k vocab (READ_MOSTLY
leverage on the giant embedding).

Sources: arXiv:2402.16819, Table 1 (32 layers, hidden 6144, 48 query heads
over 8 KV heads, FFN 24576, vocab 256,000, 4096 positions, squared ReLU with
no gate, no biases, untied input and output embeddings, RoPE); NeMo's
``Nemotron4Config15B`` (``nemo/collections/llm/gpt/model/nemotron.py``:
``rotary_percent`` 0.5, ``rotary_base`` 10000, ``layernorm1p``), whose
family defaults Hugging Face's ``NemotronConfig`` states too
(``partial_rotary_factor`` 0.5, ``hidden_act`` "relu2", ``norm_eps`` 1e-5).

``layernorm1p`` scales by ``1 + gamma`` with gamma initialised to 0: the
same function as the plain LayerNorm here, whose scale starts at 1.
"""
from repro.configs.base import ArchConfig, ModelConfig, TrainConfig, UMConfig

CONFIG = ArchConfig(
    model=ModelConfig(
        name="nemotron-4-15b",
        family="dense",
        num_layers=32,
        d_model=6144,
        num_heads=48,
        num_kv_heads=8,
        d_ff=24576,
        vocab_size=256_000,
        activation="squared_relu",
        norm="layernorm",
        rope="rope",
        rope_theta=10_000.0,
        partial_rotary_factor=0.5,
        tie_embeddings=False,
    ),
    train=TrainConfig(remat="full"),
    um=UMConfig(advises={"embedding": ("read_mostly",)}),
)
