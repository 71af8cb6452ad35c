"""Parameters, operations and bytes the ALGORITHM of a decoder of
power-retention layers (a squared-product linear attention whose state is
the symmetric square of the key: Brumby) needs, from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program, and the
state is counted at ``D = d (d + 1) / 2`` rows (8,256 at ``d = 128``)
whatever layout the program keeps (it lays out 8,320).

A configuration is the dict of ``benchmarks/configs/<name>.json``: the
published ``config.json`` keys.  A multiply-add counts as 2 FLOPs.

By hand, brumby-14b-base as cut (stream 5,120; 40 / 8 heads of 128; SwiGLU
17,408; 8 of 40 layers; 151,936 vocabulary rows):

    W_q, W_o         2 x 5,120 x 5,120                          52,428,800
    W_k, W_v         2 x 5,120 x 1,024                          10,485,760
    gate             5,120 x 8                                      40,960
    q / k head norms 2 x 128                                           256
    two norms        2 x 5,120                                      10,240
    SwiGLU           3 x 5,120 x 17,408                        267,386,880
    a layer                                                    330,352,896
    8 layers                                                 2,642,823,168
    embedding + untied head 2 x 151,936 x 5,120 = 1,555,824,640; final norm
    in all                                                   4,198,652,928

A slot's state: 8 layers x 8 heads x (8,256 x 128 + 8,256) x 4 B = 272.6 MB.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def dims(c: Dict[str, Any]):
    """(stream, query heads, key/value heads, head width, D)."""
    d = c["head_dim"]
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], d, d * (d + 1) // 2)


def mixer_matmul_params(c: Dict[str, Any]) -> int:
    """The matmul weights of ONE layer's mixer: W_q, W_k, W_v, the gate,
    W_o."""
    h, hq, hkv, d, _D = dims(c)
    return h * (hq * d + 2 * hkv * d + hkv) + hq * d * h


def layer_params(c: Dict[str, Any]) -> int:
    h, _hq, _hkv, d, _D = dims(c)
    return (mixer_matmul_params(c) + 2 * d + 2 * h
            + 3 * h * c["intermediate_size"])


def parameters(c: Dict[str, Any]) -> int:
    """Every parameter held here (the table in the module docstring)."""
    h = c["hidden_size"]
    head = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * h
    return c["num_hidden_layers"] * layer_params(c) + head + h


def dense_matmul_params(c: Dict[str, Any]) -> int:
    """Matmul weights every token of a step meets: the mixers' and the
    feed-forward parts' of all layers, and the head."""
    h = c["hidden_size"]
    return (c["num_hidden_layers"]
            * (mixer_matmul_params(c) + 3 * h * c["intermediate_size"])
            + h * c["vocab_size"])


def state_bytes(c: Dict[str, Any]) -> int:
    """ONE slot's state of ONE layer: a key/value head's ``(D, d)`` sum and
    its ``(D,)`` normaliser."""
    _h, _hq, hkv, d, D = dims(c)
    return hkv * (D * d + D) * _ITEMSIZE[c["dtype"]["power_state"]]


def slot_bytes(c: Dict[str, Any]) -> int:
    """Bytes ONE slot holds: the states of all layers (no K/V rows, no
    conv tail; whatever ``max_len`` is)."""
    return c["num_hidden_layers"] * state_bytes(c)


def state_update_bytes(c: Dict[str, Any], rows: float) -> float:
    """Least HBM traffic of the update of ``rows`` (slot, step) pairs: each
    advanced slot's state once in and once out a layer, whatever implements
    it: 2 x state bytes x slots advanced."""
    return 2.0 * rows * c["num_hidden_layers"] * state_bytes(c)


def state_update_flops(c: Dict[str, Any], rows: float) -> float:
    """Per state element and row: the decay and ``phi(k) v`` (3), and each
    of the group's ``R`` query heads' ``phi(q) S`` and its sum (2 R)."""
    _h, hq, hkv, d, D = dims(c)
    return rows * c["num_hidden_layers"] * hkv * D * d \
        * (3.0 + 2.0 * hq / hkv)


def chunk_flops(c: Dict[str, Any], positions: float, chunk: int) -> float:
    """The chunked form over ``positions`` (position x layer pairs): a
    position's state read ``2 Hq D d`` (85 M) and its part of the update
    ``2 Hkv D d`` (17 M), whatever the chunk; inside a chunk the quadratic
    form, ``4 Hq d`` a pair of positions (scores and values), half the
    ``chunk^2`` pairs causal."""
    _h, hq, hkv, d, D = dims(c)
    return positions * (2.0 * hq * D * d + 2.0 * hkv * D * d
                        + 4.0 * hq * d * chunk / 2.0)


def chunk_bytes(c: Dict[str, Any], positions: float, chunk: int) -> float:
    """Least HBM traffic of the chunked form: q, k, v in and ``o`` out
    (float32, as the layer hands them over) a position.  The state is NOT
    counted: a form that keeps it on the chip across a row's chunks moves
    it once a row, whatever ``chunk`` is (XLA's form moves it once a chunk,
    which shows as lost share)."""
    _h, hq, hkv, d, _D = dims(c)
    return positions * 4.0 * (2 * hq + 2 * hkv) * d


def decode_step_bytes(c: Dict[str, Any], rows: float) -> float:
    """Least HBM traffic of ONE decode step: every matmul weight once and
    the states of the ``rows`` slots it advances read and written once."""
    return (dense_matmul_params(c) * _ITEMSIZE[c["dtype"]["serve"]]
            + state_update_bytes(c, rows))


def decode_step_flops(c: Dict[str, Any], rows: float) -> float:
    return 2.0 * dense_matmul_params(c) * rows + state_update_flops(c, rows)


def state_bytes_share(c: Dict[str, Any], rows: float) -> float:
    """The states' share of a decode step's least bytes."""
    return state_update_bytes(c, rows) / decode_step_bytes(c, rows)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one WHOLE decode step at the slots a step of the
    window advanced (``power_names.slots_a_step``: HBM bytes or FLOPs at
    peak, the larger); None where the run says nothing."""
    from . import power_names   # what the run observed

    rows = power_names.slots_a_step(obs)
    if rows is None:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(decode_step_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
               decode_step_flops(cfg, rows) / peaks["bf16_flops_per_s"])
