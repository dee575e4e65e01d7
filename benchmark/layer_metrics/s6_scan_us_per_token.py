"""Device time of the chunked S6 recurrence a prompt token a layer: self time under mamba_scan in the traced window's chunk forwards over their ssm_chunk_tokens and the block's S6 layers, in microseconds."""

from benchmark import s6_readers


def reduce(ctx):
    return s6_readers.scan_us_per_token(ctx)
