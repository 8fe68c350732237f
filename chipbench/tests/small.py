"""Tiny sizes of the benchmark's cells for CPU runs (interpret mode)."""

FACADE = {"config": {"cache": {"capacity": 1024}},
          "traffic": {"arrivals": {"rate": 200.0}}}

ENGINE = {"config": {"cache": {"capacity": 256},
                     "engine": {"max_batch": 8, "max_new_tokens": 6},
                     "model": {"hidden_size": 128, "intermediate_size": 256,
                               "num_attention_heads": 4,
                               "num_key_value_heads": 2,
                               "num_hidden_layers": 2, "vocab_size": 512},
                     "check": {"sequences": 4}},
          "traffic": {"arrivals": {"rate": 20.0}, "max_call": 24,
                      "prompt": {"median": 8, "sigma": 0.5, "min": 4,
                                 "max": 16}}}


def args(cell: str, seed: int, seconds: float = 4.0, trace: int = 0):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
