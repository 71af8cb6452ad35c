"""The readers of the ``sambay_*`` metrics: a decoder-hybrid-decoder's own
layers in a device trace, told by the SCOPE the program traced them under
(``ray_tpu/observability/device.py`` ``SCOPES``; ``lib/scope_names.py``
joins the compiled instructions' scopes to the trace's events):

- ``cross_attention``: in a decode step the EIGHT reads of the one
  full-length K/V pool -- the K/V layer's own and the seven cross layers',
  the ``decode_attention`` kernel called inside that scope, which keeps
  what lies inside it (``device.scope_of``); in a prefill the seven cross
  layers' at each row's last position;
- ``decode_attention`` / ``attention``: the window layers' reads of their
  rings, the same kernel as every other configuration's attention;
- ``mamba1_scan`` (prefill) and ``mamba1_state_update`` (decode): the
  Mamba-1 recurrence, XLA's in both;
- ``gmu``: a gated memory unit; ``diff_combine``: the subtraction and
  norm after every attending layer's two softmaxes.

A program without these scopes (another configuration, an older commit)
matches nothing and the readers return None.
"""

from __future__ import annotations

from typing import Optional, Sequence

from . import program_spans, readers, sambay_flops, scope_names, swa_names


def _scope_share(obs, which: str, scopes: Sequence[str]) -> Optional[float]:
    """Own device seconds of ``scopes`` / device seconds of the module's
    runs, as a fraction; None where none of them ran."""
    if "mb_per_layer" not in obs["cell"].config:
        return None
    got = scope_names.split(obs, which)
    if not got or not got.module_s:
        return None
    seconds = sum(s for (scope, _phase), s in got.by.items()
                  if scope in scopes)
    return seconds / got.module_s if seconds else None


def _percent(share: Optional[float]) -> Optional[float]:
    return None if share is None else 100.0 * share


def _traced_lengths(obs):
    span = obs.get("trace_span")
    if not span or span[0] is None:
        return None
    return swa_names.lengths_in_flight(obs, (span[0] + span[1]) / 2) or None


def _read_roofline(obs, scopes: Sequence[str], least_bytes):
    """Least time of the reads ``least_bytes(config, lengths in flight)``
    counts (at the HBM peak: one query a row is bandwidth-bound at any
    batch) / the measured time a step of the decode ops under ``scopes``."""
    share = _scope_share(obs, "decode", scopes)
    step_ms = readers.decode_step_device_ms(obs)
    lengths = _traced_lengths(obs) if share else None
    if not share or step_ms is None or lengths is None:
        return None
    least = least_bytes(obs["cell"].config, lengths) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (share * step_ms * 1e-3)


WINDOW_SCOPES = ("decode_attention", "attention")


# --------------------------------------------------------------- readers
def shared_kv_attention_time_share(obs) -> Optional[float]:
    return _percent(_scope_share(obs, "decode", ("cross_attention",)))


def window_attention_time_share(obs) -> Optional[float]:
    return _percent(_scope_share(obs, "decode", WINDOW_SCOPES))


def ssm_scan_time_share(obs) -> Optional[float]:
    return _percent(_scope_share(obs, "prefill", ("mamba1_scan",)))


def ssm_state_update_time_share(obs) -> Optional[float]:
    return _percent(_scope_share(obs, "decode", ("mamba1_state_update",)))


def gmu_time_share(obs) -> Optional[float]:
    return _percent(_scope_share(obs, "decode", ("gmu",)))


def diff_combine_time_share(obs) -> Optional[float]:
    return _percent(_scope_share(obs, "decode", ("diff_combine",)))


def shared_kv_attention_roofline(obs) -> Optional[float]:
    """A step's eight reads of the shared pool: each live row's K and V
    once a reading layer / the ops under ``cross_attention``."""
    return _read_roofline(obs, ("cross_attention",),
                          sambay_flops.shared_kv_bytes)


def window_attention_roofline(obs) -> Optional[float]:
    """A step's reads of the rings: each live row's last ``sliding_window``
    keys and values once a window layer / the ops under
    ``decode_attention`` and ``attention``."""
    return _read_roofline(obs, WINDOW_SCOPES, sambay_flops.window_kv_bytes)


def prefill_skipped_share(obs) -> Optional[float]:
    """serve.prefill_group: positions x layers the window's prefills did
    not compute (``positions_skipped``: the layers after the K/V layer run
    at a row's last position alone) / positions x layers of the groups
    (``token_positions`` x ``layers``), in %."""
    got = program_spans.collect(obs)
    groups = [g for g in (got.groups if got else [])
              if g.get("positions_skipped") is not None and g.get("layers")]
    if not groups:
        return None
    return 100.0 * sum(g["positions_skipped"] for g in groups) \
        / sum(g["token_positions"] * g["layers"] for g in groups)
