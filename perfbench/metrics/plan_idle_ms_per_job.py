"""Chip 0's idle time per traced job while the host is in the plan: the
idle gaps split at the host spans' boundaries, the pieces whose
innermost span is the plan's (``shuffle:plan``, its children, or
``shuffle:splitters``) summed. The host's share of the plan. None where
the plan left the chip no idle time."""

from perfbench import phases


def read(run):
    return phases.plan_idle_ms_per_job(run)
