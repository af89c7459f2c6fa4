"""Bridging helpers: existing accounting surfaces -> registry (ISSUE 11).

The padding/byte/touched-row report (`DistributedEmbedding.
exchange_padding_report`) is the repo's static model of every per-step
volume; `export_exchange_gauges` publishes its headline fields as
registry gauges so SLO rules and bench snapshots address them the same
way they address runtime counters — and so the consistency test
(tests/test_exchange.py) can assert the gauges a driven run exported
EQUAL a fresh report's fields (the wiring, not the model, is what can
silently rot).
"""

from typing import Optional

from distributed_embeddings_tpu.obs.registry import MetricRegistry

__all__ = ["export_exchange_gauges", "export_kernel_gauges",
           "export_moe_gauges", "export_update_gauges",
           "EXCHANGE_GAUGE_FIELDS", "EXCHANGE_GROUP_GAUGE_FIELDS"]


def export_kernel_gauges(registry: MetricRegistry) -> dict:
    """Set ``kernels/gate_verdict{impl=}`` gauges from the sparse-update
    kernel gates (ISSUE 12): 1 = hardware-validated, 0 = probe failed,
    -1 = never probed (off-TPU interpret mode / impl never requested).
    ``tools/slo_tier1.json`` requires the pallas verdict's PRESENCE, so
    a run that forgot this wiring fails the smoke loudly rather than
    shipping a snapshot that cannot say which kernel family ran.
    Returns the verdict dict."""
    from distributed_embeddings_tpu.ops.sparse_update import gate_verdicts
    verdicts = gate_verdicts()
    for impl, verdict in verdicts.items():
        registry.gauge("kernels/gate_verdict", impl=impl).set(verdict)
    return verdicts

def export_moe_gauges(registry: MetricRegistry, stats: dict) -> dict:
    """Set ``moe/held_pairs_share{layer=}``,
    ``moe/max_expert_load_share{layer=}`` and, of sigmoid routers,
    ``moe/bias_moved_share{layer=}`` from one batch's
    `routing_stats` (`models.mellum.Mellum.routing_stats` or
    `models.lfm2.Lfm2.routing_stats`, jitted and forward only: ``{name:
    [layers]}``). The first says how far this chip's load is from an
    even router's ``held / total`` (the sorted pair stream's usual rows
    hold twice that: `ExpertLayer.fast_rows`), the second how uneven the
    held experts are among themselves, the third for how many tokens the
    bias chose another set than the scores alone. A host read of a device
    result: call it beside a loss fetch, not every step. Returns ``{name:
    [floats]}``."""
    out = {}
    for name, per_layer in stats.items():
        out[name] = [float(v) for v in per_layer]
        for layer, value in enumerate(out[name]):
            registry.gauge(f"moe/{name}", layer=layer).set(value)
    return out


def export_update_gauges(registry: MetricRegistry, shares: dict,
                         stream_orders: Optional[dict] = None) -> dict:
    """Set ``update/dup_share{bucket=}`` from one batch's
    `DistributedEmbedding.duplicate_shares` (jitted and forward only:
    ``{bucket: share}``): 1 - distinct rows / valid slots of the id stream
    a bucket's sparse update receives, the part of it that the duplicate
    sum (`dedup_sum`'s scan, or the tile stream's one-hot product) folds
    away. A host read of a device result: call it beside a loss fetch,
    not every step. Returns ``{bucket: float}``.

    `stream_orders` (`DistributedEmbedding.stream_orders(batch)`, static)
    sets ``lookup/stream_order{bucket=}`` beside it: 1 where that stream
    is flattened feature-major, (f, k, b), 0 where batch-major."""
    out = {bucket: float(share) for bucket, share in shares.items()}
    for bucket, value in out.items():
        registry.gauge("update/dup_share", bucket=bucket).set(value)
    for bucket, order in (stream_orders or {}).items():
        registry.gauge("lookup/stream_order", bucket=bucket).set(int(order))
    return out


# top-level report fields exported as exchange/<field> gauges
EXCHANGE_GAUGE_FIELDS = (
    "true_ids", "exchanged_ids", "ratio",
    "exchanged_bytes", "true_bytes", "act_wire_reduction",
    "touched_rows_per_step", "delta_bytes_per_step",
    "occupancy", "slack_rows", "evictions_per_step",
    "prefetch_patch_rows_per_step", "prefetch_patch_bytes_per_step",
)

# per-group fields exported with a group= label
EXCHANGE_GROUP_GAUGE_FIELDS = (
    "touched_rows_per_step", "occupancy",
    "prefetch_patch_rows_per_step",
)


def export_exchange_gauges(registry: MetricRegistry, emb, *,
                           batch: int = 1, vocab=None, lookahead: int = 0,
                           hot_hit_rate=None,
                           hotness: Optional[list] = None) -> dict:
    """Set ``exchange/*`` gauges from one `exchange_padding_report`
    call (same arguments, same numbers); per-group entries land under a
    ``group=<index>`` label with the bucket index alongside. Returns
    the report so callers embedding it (bench records, fit history)
    don't recompute it."""
    rep = emb.exchange_padding_report(hotness=hotness,
                                      hot_hit_rate=hot_hit_rate,
                                      batch=batch, vocab=vocab,
                                      lookahead=lookahead)
    for field in EXCHANGE_GAUGE_FIELDS:
        registry.gauge(f"exchange/{field}").set(rep[field])
    for gi, entry in enumerate(rep["groups"]):
        for field in EXCHANGE_GROUP_GAUGE_FIELDS:
            registry.gauge(f"exchange/{field}", group=gi,
                           bucket=entry["bucket"]).set(entry[field])
    return rep
