"""Device ms per step in one class of operations (``params["class"]``).
Each event is charged to the innermost class that covers it, so the classes
add up to ``step.device_ms``. Over several chips the value is their mean,
which keeps that sum, or with ``"chips": "worst"`` the chip that spent most:
the one the others wait for."""


def read(ctx, params):
    if not ctx.chips:
        return None
    ns = [chip.class_ns.get(params["class"], 0.0) for chip in ctx.chips]
    over = max(ns) if params.get("chips") == "worst" else sum(ns) / len(ns)
    return over / ctx.steps * 1e-6
