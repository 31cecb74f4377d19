"""Per-architecture dry-run presets (dtypes, accumulation, strategy knobs;
dryrun.py flags override them) and the settings ``run_training`` uses on
the CPU and on one chip.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Preset:
    moment_dtype: str = "float32"
    grad_accum_dtype: str = "float32"
    remat: str = "block"
    fsdp: bool = True
    ep: bool = True
    # microbatch sequences per accumulation step; None => one seq per DP shard
    microbatch: Optional[int] = None
    q_chunk: int = 1024
    # §Perf winners: pure-DP+FSDP training for small models (removes the
    # per-token TP activation all-reduces; train shapes only) and
    # expert-splitting so grok's 8 experts EP-shard the 16-way axis.
    dp_only_train: bool = False
    expert_split: int = 1


# >=300B configs: bf16 moments + bf16 accumulation to fit 256 x 16GB HBM.
_BIG = Preset(moment_dtype="bfloat16", grad_accum_dtype="bfloat16",
              remat="full")

PRESETS = {
    # >=30B dense: full remat (checkpoint-dots pushed chameleon/qwen3 train
    # past 16GB/chip at baseline)
    "chameleon-34b": Preset(remat="full"),
    "starcoder2-7b": Preset(dp_only_train=True, remat="full"),
    "internlm2-1.8b": Preset(dp_only_train=True, remat="full"),
    "qwen3-32b": Preset(remat="full"),
    "gemma2-9b": Preset(),
    "jamba-1.5-large-398b": _BIG,
    "seamless-m4t-large-v2": Preset(dp_only_train=True, remat="full"),
    # grok: 8 experts split 2-way => 16-way EP (2.2x collective win, §Perf)
    "grok-1-314b": dataclasses.replace(_BIG, expert_split=2),
    # 480B: blockwise-int8 AdamW moments (bf16 moments left 25.8GB/chip)
    "arctic-480b": dataclasses.replace(_BIG, moment_dtype="int8"),
    "falcon-mamba-7b": Preset(),
}


def preset_for(arch_name: str) -> Preset:
    return PRESETS.get(arch_name, Preset())


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
