"""Device, training cell: ``hbm_peak_gib``'s reading under the name that
moves ``train_tok_s``."""

from chipbench.layer_metrics.hbm_peak_gib import read  # noqa: F401
