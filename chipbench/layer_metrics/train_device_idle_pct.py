"""Device, training cell: ``device_idle_pct``'s reading under the name
that moves ``train_tok_s`` (a per-layer metric names ONE end-to-end
metric, and no single one exists in serving and training cells)."""

from chipbench.layer_metrics.device_idle_pct import read  # noqa: F401
