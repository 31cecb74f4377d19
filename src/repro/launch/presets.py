"""Training settings: the reduced CPU path and full width on one chip."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    """What ``launch/train.run_training`` builds its state and step from."""
    param_dtype: str
    moment_dtype: str
    grad_accum_dtype: str
    remat: str
    q_chunk: int          # upper bound; the step uses min(seq_len, q_chunk)
    seq_len: int
    batch_size: int       # sequences per step (one microbatch)


# The reduced-width CPU path (tests, examples): everything in float32.
REDUCED_TRAIN = TrainSettings(
    param_dtype="float32", moment_dtype="float32", grad_accum_dtype="float32",
    remat="none", q_chunk=128, seq_len=128, batch_size=4)

# internlm2-1.8b at full width on ONE v5e chip (15.75 GB HBM usable), as
# the TPU compiler sizes it for a described v5e chip: the float32 state
# (float32 params and moments, no remat) needed 29.65 GB; bf16 params +
# bf16 moments + bf16 accumulation + full remat still needed 17.0 GB at a
# single 4096-token sequence. Blockwise-int8 AdamW moments
# (training/quant.py) make it fit at 2 x 4096 tokens per step; 3 sequences
# need 18.2 GB, since each adds ~2.6 GB of float32 logits and their
# gradient. q_chunk 512 keeps the attention scores of the backward small.
ONE_CHIP_TRAIN = TrainSettings(
    param_dtype="bfloat16", moment_dtype="int8", grad_accum_dtype="bfloat16",
    remat="full", q_chunk=512, seq_len=4096, batch_size=2)
