"""95th percentile, over every request due in the window that the engine
admitted, of its wait in the engine's queue: the engine's admission
stamp minus its submission stamp, both on the engine's clock
(milliseconds). A program without an admission stamp reports nothing."""
from chipbench.stats import percentile


def read(rec):
    waits = [s.req.t_admit - s.req.t_submit for s in rec.due_in_window()
             if getattr(s.req, "t_admit", 0.0) > 0.0]
    p = percentile(waits, 95)
    return None if p is None else 1e3 * p
