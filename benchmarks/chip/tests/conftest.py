"""Puts the benchmark's modules and the program's ``src`` on the path.

Run from the root of the checkout, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

TINY = dict(name="tiny", program_arch="whisper-base", hidden_size=64,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=256, num_hidden_layers=2, encoder_layers=2,
            encoder_frames=32, vocab_size=300, rope_theta=10000.0,
            norm_eps=1e-6, gated_mlp=False, tie_word_embeddings=False,
            dtype="bfloat16", reduced=[])
TINY_MIX = dict(algorithm="fedadam_ssm", alpha=0.05, clients=3,
                local_epochs=2, batch=2, seq=16, non_iid=True,
                exact_topk=False, sparsify_backend="reference",
                remat="none", lr=1e-3, driver="scan", check_rounds=3,
                batches=3)
