"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds."""

from bench import harness


def tiny_spec(workload: str, *, rate: float = 4.0) -> dict:
    spec = harness.load_cell(workload)
    m = spec["config"]["model"]
    spec["config"] = dict(spec["config"], model=dict(
        m, n_layers=4, d_model=128, n_heads=4,
        n_kv_heads=2 if m["qk_norm"] else 4, head_dim=32, d_ff=256,
        vocab=512, edge_blocks_float=1))
    spec["cell"] = dict(spec["cell"], engine=dict(
        spec["cell"]["engine"], max_batch=2, max_len=64))
    tr = spec["traffic"]
    spec["traffic"] = dict(
        tr, prompt_tokens=dict(tr["prompt_tokens"], median=24, min=17,
                               max=32),
        output_tokens=dict(tr["output_tokens"], median=12, min=8, max=16))
    if tr["arrivals"]["kind"] == "poisson":
        spec["traffic"]["arrivals"] = dict(tr["arrivals"], rate_rps=rate)
    return spec
