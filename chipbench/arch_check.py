"""The dense decoder's checks that the program runs what a configuration
file states. They hold for a family whose module defines neither
``WIDTHS`` nor ``check_arch`` (``families/__init__.py``), and a family
with layers of its own may build on them.
"""
from __future__ import annotations

# configuration key -> the program's ArchConfig field; each must agree
WIDTHS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta"}


def check_widths(c: dict, arch, widths: dict) -> None:
    """Raises ``ValueError`` naming the first key of ``widths`` whose
    value in ``c`` differs from its ``arch`` field."""
    for key, field in widths.items():
        if float(getattr(arch, field)) != float(c[key]):
            raise ValueError(f"{c['registry_arch']}: {key}={c[key]} but "
                             f"the program runs {field}="
                             f"{getattr(arch, field)}")


def check_arch(c: dict, arch) -> None:
    """Raises ``ValueError`` where ``arch`` would run another head size,
    tied embeddings, activation, norm, attention bias or window than
    ``c`` states; only the RMSNorm epsilon, which the program
    hard-codes, is not compared."""
    dh = int(c.get("head_dim") or c["hidden_size"] //
             c["num_attention_heads"])
    if arch.d_head != dh or arch.tie_embeddings != c["tie_word_embeddings"]:
        raise ValueError("head size or tied embeddings differ from the "
                         "program")
    if (arch.activation != "swiglu" or arch.norm != "rmsnorm"
            or arch.qkv_bias or c.get("attention_bias")):
        raise ValueError("the decoder checks expect SwiGLU, RMSNorm and no "
                         "attention bias")
    win, cap = c.get("sliding_window"), int(c["engine"]["max_seq_len"])
    if arch.sliding_window != win:
        # a window no position of this engine can reach masks nothing:
        # the program may run the layer without one
        if not (arch.sliding_window is None and win is not None
                and win >= cap - 1):
            raise ValueError(f"sliding_window={win} but the program runs "
                             f"{arch.sliding_window}")
