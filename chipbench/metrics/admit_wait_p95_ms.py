"""admit_wait_p95_ms (ms, scheduler): 95th percentile of submission to admission into a slot (SeqState.t_admit - t_submit, host clock), over requests due in the window; none where the program has no stamps."""
from chipbench.stamps import admit_wait_p95_ms as read  # noqa: F401
