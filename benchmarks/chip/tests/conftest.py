"""Imports for the benchmark's own tests: the benchmark's modules by
their flat names, the program from ``src``. Run from the repository root:

    python -m pytest benchmarks/chip/tests
"""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))


def tiny_spec(pattern: str = "swa", minibatch: int = 2) -> dict:
    """A cell at a size the CPU holds: same code paths, toy widths.
    ``pattern``: the layer kinds of one period, joined by ``+``; the stack
    is one whole period and one layer more (two layers of a one-kind
    pattern)."""
    kinds = pattern.split("+")
    family = {("swa",): "dense", ("rwkv",): "ssm"}.get(tuple(kinds),
                                                      "hybrid")
    name = f"tiny-{pattern}"
    arch = {"name": name, "family": family,
            "num_layers": len(kinds) + 1, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 2 if "swa" in kinds else 4, "head_dim": 16,
            "d_ff": 128, "vocab_size": 256, "layer_pattern": kinds,
            "swa_window": 48, "rope_theta": 10000.0, "ffn_type": "silu",
            "param_dtype": "float32", "surrogate_dtype": "bfloat16",
            "source": "test"}
    traffic = {"method": "fsgld", "dynamics": "sgld", "surrogate": "scalar",
               "executor": "packed", "federation": "identity",
               "reassign": "permutation", "step_size": 1e-5,
               "prior_precision": 1.0, "alpha": 1.0, "temperature": 1.0,
               "clients": 4, "sequences_per_client": 16, "seq_len": 64,
               "dirichlet_alpha": 0.1, "minibatch": minibatch,
               "local_steps": 2, "fit": {"burn": 2, "minibatch": minibatch}}
    cell = {"name": name, "config": name,
            "traffic": "tiny", "chips": 1}
    return {"bench": {"workloads": [cell], "end_to_end": [], "per_layer": []},
            "cell": cell, "config": {"name": name, "arch": arch},
            "traffic": traffic,
            "limits": {"grad_gap": 1.0, "change_gap": 1.0,
                       "state_gap": 1.0}}
