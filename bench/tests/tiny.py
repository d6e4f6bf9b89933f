"""Smoke-size cells for the CPU rehearsals: the published mixes with
the model cut to a few thousand parameters and the windows to seconds.

Importing it puts the benchmark and the program on the path, so the
tests import it first."""
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402
import model  # noqa: E402
import traffic  # noqa: E402

DIMS = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512}
PROGRAM = {"d_model": 64, "d_ff": 128, "n_heads": 4, "n_kv_heads": 2,
           "head_dim": 16, "n_layers": 2, "vocab_size": 512}


# wide enough for the rehearsals' sound runs, which decode in bf16 at
# smoke width
LIMITS = {"max_gap": 0.5, "mean_gap": 0.05}


def conf():
    c = copy.deepcopy(model.load_config("internlm2_1_8b"))
    c["config"].update(DIMS)
    c["fixed"] = {"head_dim": 16, "rotary_dim": 16}
    c["program"]["overrides"] = dict(PROGRAM)
    return c


def cell(mix_name: str, **mix_over) -> harness.Cell:
    """The named mix at smoke size: small batches and lengths, the
    loop and distributions as the mix states."""
    mix = copy.deepcopy(traffic.load_mix(mix_name))
    mix["engine"] = {"max_batch": 4, "max_len": 64}
    mix["clients"] = 4
    mix["prompt_len"] = {"values": [3, 5], "weights": [0.5, 0.5]}
    mix["output_len"].update(median=8, min=2, max=24)
    mix["check"].update(pad_to=64, rows=2)
    mix.update(mix_over)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    return harness.Cell("tiny", 1, conf(), mix, LIMITS,
                        bench["end_to_end"], bench["per_layer"])
