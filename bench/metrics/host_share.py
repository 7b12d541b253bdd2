"""Share of the window in which the training loop was not inside a step:
(window - the steps' own times) / window, the steps' times as
``Session.train`` reports them (dispatch to device completion)."""


def read(run):
    w = run.out.window_s
    return 100.0 * (w - sum(run.out.window_step_times)) / w
