"""Pallas TPU kernels.  ``flash_attention_bwd`` is the train step's
attention on the chip, chosen by ``repro.models.attention``;
``ssd_chunk`` is the Mamba-2 SSD core on the chip, chosen by
``repro.models.ssm``; ``paged_attention`` is the paged-decode kernel,
not yet on the serving path; ``ref`` holds the pure-jnp oracles the
tests hold both to.  Only ``repro.models`` imports this package."""
