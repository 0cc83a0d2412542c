"""Whole-step share of the chip's roofline in decode: for every call of the
window, the prefill and each decode step bounded below by
max(required FLOPs / peak FLOP/s, required bytes / peak bytes/s), summed,
over the window's wall time.  Required bytes are the weights once per
launch and the cache positions actually filled, not the cache's capacity."""


def read(facts):
    if facts.get("kind") != "decode" or not facts.get("peak"):
        return None
    peak = facts["peak"]
    least = sum(max(f / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
                for f, b in facts["call_work"])
    return 100.0 * least * facts["calls"] / facts["window_s"]
