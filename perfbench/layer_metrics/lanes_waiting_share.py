"""Scheduler and cache: mean share of the lanes that stood FREE at a tick's
dispatch with a request queued for them (``lanes_waiting`` of each
``serving.decode`` span over the lanes): the head of the queue was refused
in that step, for the step's one prefill slot, for pages or for its images'
keys, which the span's ``waiting_on`` and the run's ``lane_steps_waiting_*``
counters tell apart. What ``lane_occupancy``, ``lanes_prefilling_share``
and this leave of 1 are lanes whose request's last token was in flight, and
free lanes nobody asked for (the closed loop's own clients)."""

from perfbench.layer_metrics.lanes_prefilling_share import lanes_share


def read(run):
    return lanes_share(run, "lanes_waiting")
