"""A package of families outside `acpbench/`, as a later PR's would be
inside it: `test_harness_cpu.py` adds it to `spec.FAMILY_PACKAGES`."""
