import os

# Tests run on the default (single) CPU device — the dry-run alone forces
# 512 host devices, in its own process. Keep any inherited flag out.
os.environ.pop("XLA_FLAGS", None)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compilation cache in tests, whatever an entry point enables
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

try:
    from hypothesis import HealthCheck, settings
except ImportError:  # property tests skip themselves via importorskip
    pass
else:
    settings.register_profile(
        "ci",
        max_examples=25,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )
    settings.load_profile("ci")
