"""Configuration kinds: ``bench/kinds/<kind>.py`` drives every configuration
whose file names that ``kind``. Each module defines ``Case(config, traffic,
mesh, seed)`` with ``setup()`` (build, make the inputs from the seed, warm
every shape), ``step()`` (dispatch one step, return an array to wait on),
``programs()`` (the jitted programs the window runs, with arguments to
lower them by, so that the trace's ops can be given their JAX scopes),
``collect()`` (host copies of what is compared; frees the device state),
``compare(host)`` (the compared numbers against the float64 reference) and
``control(host)`` (the same numbers with the bfloat16 reference in the
program's place)."""
