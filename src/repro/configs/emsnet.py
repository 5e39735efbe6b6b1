"""EMSNet — the paper's own multimodal multitask model.

Text encoder (TinyBERT/MobileBERT/BERTBase-class bidirectional
transformer), vitals encoder (RNN/LSTM/GRU), scene encoder (FC over the
object-detection one-hot), concatenation fusion, three headers:
protocol (46-way), medicine type (18-way), quantity (regression).
Dims follow the paper's candidates (Table 1); defaults are the
TinyBERT-GRU-FC combination the paper highlights for on-device use.
"""
from dataclasses import dataclass, field
from typing import Tuple


@dataclass(frozen=True)
class EMSNetConfig:
    name: str = "emsnet"
    # text encoder (bidirectional transformer)
    text_encoder: str = "tinybert"        # tinybert | mobilebert | bertbase
    vocab_size: int = 8192
    max_text_len: int = 64
    # vitals encoder
    vitals_encoder: str = "gru"           # rnn | lstm | gru
    n_vitals: int = 6                     # BP, HR, PO, RR, CO2, BG
    vitals_len: int = 30                  # up to 30 vitals per event (NEMSIS)
    vitals_hidden: int = 64
    # scene encoder
    scene_dim: int = 3                    # alcohol / pill / medicine-bottle
    scene_hidden: int = 16
    # tasks
    n_protocols: int = 46                 # paper follows EMSAssist: 46
    n_medicines: int = 18
    # training
    dropout: float = 0.0
    dtype: str = "float32"
    # text-attention backend: route _bert_block through the Pallas
    # flash kernel (key-padding-masked). Whether the kernel is
    # interpreted is decided by kernels.ops from the backend.
    use_flash_text: bool = False
    # ragged text attention: flash_segments routes the *natural* (B, S)
    # path through the segment-masked flash kernel at the same fixed
    # flash_block the packed ragged layout uses. Fixed per-block
    # reduction shapes make a packed ragged call bit-identical to the
    # per-row reference on XLA-CPU, so a bit-parity (atol 0) reference
    # config must set use_flash_text=True, flash_segments=True with the
    # same flash_block as the ragged engine's ragged_align.
    # flash_block is the segment kernel's block on both axes; the TPU
    # compiler needs its key block to be a multiple of 128.
    flash_segments: bool = False
    flash_block: int = 128

    @property
    def text_dims(self) -> Tuple[int, int, int, int]:
        """(layers, d_model, heads, d_ff) for the text encoder."""
        return {
            "microbert": (2, 64, 4, 128),      # CPU-bench tier (not in paper)
            "tinybert": (4, 312, 12, 1200),
            "mobilebert": (24, 128, 4, 512),
            "bertbase": (12, 768, 12, 3072),
        }[self.text_encoder]

    @property
    def feature_dims(self):
        """|F_T|, |F_V|, |F_I| — concatenated into F_C."""
        return {
            "text": self.text_dims[1],
            "vitals": self.vitals_hidden,
            "scene": self.scene_hidden,
        }


def config(**kw) -> EMSNetConfig:
    return EMSNetConfig(**kw)


def tiny(**kw) -> EMSNetConfig:
    """Fast CPU-test variant."""
    base = dict(vocab_size=256, max_text_len=16, vitals_len=8,
                vitals_hidden=16, scene_hidden=8)
    base.update(kw)
    return EMSNetConfig(**base)
