import jax

# Deterministic dtype policy for the whole suite: several test modules need
# f64 (solver exactness); module import order at collection must not change
# behaviour, so x64 is enabled globally and f32-targeted tests pin dtypes.
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test (subprocess)")

