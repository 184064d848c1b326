"""A cell at a size a CPU test run can hold: two layers of width 64, the
same engine, traffic generator, seam, window and check as a chip run."""

import json

from bench import harness

CONFIG = {
    "name": "tiny", "model_type": "llama", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "vocab_size": 300, "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "serving": {"storage_bits": 8, "variant": "booth", "kv": "int8", "page_size": 8,
                "prefill_chunk": 8},
}
TRAFFIC = {
    "slots": 4, "tier_bits": 8,
    "prompt": {"median": 12, "min": 6, "max": 24}, "output": {"median": 8, "min": 4, "max": 16},
    "check_requests": 3, "control_bits": 4,
}
#: between the sound program's widest gap and the control's at this size
LIMITS = {"max_logit_gap": 0.5}
SEED = 2**33 + 12345  # wider than 32 bits, as benchmark seeds may be


def cell(name="llama-7b-8L.w8.isl128-osl128", config=None, **traffic):
    spec = json.load(open(harness.ROOT / "BENCHMARK.json"))
    return harness.Cell(name, 1, dict(config or CONFIG), {**TRAFFIC, **traffic}, dict(LIMITS),
                        [m for m in spec["per_layer"] if name in m["workloads"]],
                        [m for m in spec["end_to_end"] if name in m.get("workloads", [name])])


def run(seconds=1.5, trace=False, control=False, config=None, **traffic):
    c = cell(config=config, **traffic)
    return harness.run(c.name, SEED, seconds, trace, control=control, cell=c,
                       require_tpu=False, compile_cache=False)
