"""Seconds of ``fit``'s ``bringup/init_state`` phase: ``create_train_state``
— the shapes for the shardings, the model's own init traced, lowered,
compiled or loaded and dispatched — which every cell then replaces with the
harness's weights (the ``bringup`` telemetry row's phases). Nothing where
the program writes no such row."""

from benchmarks.layer_metrics.fit_bringup_s import phase_s

SPANS = ("bringup/init_state",)


def read(ctx):
    return phase_s(ctx, SPANS)
