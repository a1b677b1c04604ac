"""chipbench.reference: each configuration's plain reference."""
