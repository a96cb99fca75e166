"""submit_lag_p95_ms (ms, open-loop client): 95th percentile of due time to submission (SeqState.t_submit - due): how late the generator ran; none where the program has no stamps."""
from chipbench.stamps import submit_lag_p95_ms as read  # noqa: F401
