"""Operation counts of one denoiser or decode step, kept with the
benchmark so that no change to the program moves the yardstick.

Copied from the program's analytic cost model (``core/complexity.py``)
and stated over the configuration file's sizes. One departure: the
Mamba-2 input projection is counted at the width the model has, one
group of B/C streams (``2·d_inner + 2·state + heads``), where the
program's model counts a B/C pair per head; and a decode step's mixer
is counted as the recurrence it runs (state decay, outer-product
update and readout, ``6·d_inner·state``), not as the chunked scan; a
drafted decode step counts only the work whose result it uses.
"""
from __future__ import annotations


def _dit_block(s, tokens: int) -> float:
    d, H = s["d_model"], s["num_heads"]
    hd = d // H
    proj = 2.0 * tokens * d * hd * (2 * H + 2 * H)
    scores = 2.0 * tokens * tokens * H * hd * 2
    ffn = 2.0 * tokens * d * s["d_ff"] * 2
    return proj + scores + ffn


def _dit_glue(s, tokens: int) -> float:
    d = s["d_model"]
    p2c = s["patch_size"] ** 2 * s["in_channels"]
    return 2.0 * tokens * d + 2.0 * tokens * p2c * d * 2 \
        + 2.0 * s["num_layers"] * d * 6 * d


def dit_tokens(s) -> int:
    return (s["latent_size"] // s["patch_size"]) ** 2


def dit_full_flops(s) -> float:
    """One denoiser forward of one sample."""
    t = dit_tokens(s)
    return s["num_layers"] * _dit_block(s, t) + _dit_glue(s, t)


def dit_draft_flops(s) -> float:
    """One drafted step of one sample: the verify layer, the glue and
    the Taylor evaluation of every layer's two increments."""
    t = dit_tokens(s)
    taylor = 4.0 * s["num_layers"] * 2 * t * s["d_model"]
    return _dit_block(s, t) + _dit_glue(s, t) + taylor


def _mamba2_state_advance(s) -> float:
    """Input projection, then the state's decay and update."""
    d = s["d_model"]
    di = s["ssm_expand"] * d
    ns = s["ssm_state"]
    nh = di // s["ssm_head_dim"]
    return 2.0 * d * (2 * di + 2 * ns + nh) + 4.0 * di * ns


def _mamba2_mixer(s) -> float:
    """State advance, readout and output projection."""
    di = s["ssm_expand"] * s["d_model"]
    return _mamba2_state_advance(s) + 2.0 * di * s["ssm_state"] \
        + 2.0 * di * s["d_model"]


def _decode_glue(s) -> float:
    return 2.0 * s["d_model"] + 2.0 * s["d_model"] * s["vocab_size"]


def decode_full_flops(s) -> float:
    """One full decode step of one sequence."""
    return s["num_layers"] * _mamba2_mixer(s) + _decode_glue(s)


def decode_draft_flops(s) -> float:
    """One drafted decode step: the verify layer's mixer, every other
    layer's state advance (its output is replaced by the forecast, so
    its readout is not counted), the glue and the Taylor evaluation."""
    taylor = 4.0 * s["num_layers"] * 2 * s["d_model"]
    return _mamba2_mixer(s) \
        + (s["num_layers"] - 1) * _mamba2_state_advance(s) \
        + _decode_glue(s) + taylor


def taylor_predict_cost(table_shape, table_itemsize: int,
                        out_itemsize: int, positions: int = 1):
    """(flops, bytes) of one call of the fused Taylor predict kernel from
    its live operands: the table [m+1, ...] is read once, ``positions``
    forecasts of one plane's shape are written; each forecast element
    takes m+1 multiplies and m adds."""
    m1 = table_shape[0]
    plane = 1
    for n in table_shape[1:]:
        plane *= n
    flops = float(positions * plane * (2 * m1 - 1))
    nbytes = float(m1 * plane * table_itemsize
                   + positions * plane * out_itemsize)
    return flops, nbytes
