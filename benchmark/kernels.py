"""Name patterns that find the library's kernels and programs in a trace.

Each is a tuple of regular expressions for
:meth:`benchmark.trace_reduce.Summary.seconds`.  A device op's name in
the trace is its HLO instruction's text; a Pallas call's instruction is
named after the function that makes the call (``%grouped_l2_scan_fused.1
= (...) custom-call(...), custom_call_target="tpu_custom_call"``).
"""

# the fused IVF-PQ list scans with in-kernel top-k
# (pq_group_scan_pallas.grouped_l2_scan_fused,
# pq_code_scan_pallas.grouped_code_scan_fused)
SCAN = (r"^%grouped_l2_scan_fused\.", r"^%grouped_code_scan_fused\.")

# the fused CAGRA hop (cagra_hop_pallas.fused_hop)
HOP = (r"^%fused_hop\.",)

# the jitted refine program (neighbors/refine._refine_impl), by module
REFINE = (r"_refine_impl",)
