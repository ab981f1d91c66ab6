"""True positives: an MU step whose passes over X sit outside the
"products" scope (they would read as factor algebra), and one that never
enters the "mu" scope (it drops out of the per-phase device time)."""
import jax


@jax.named_scope("mu")
def mu_step_unscoped_products(X, A, R, eps=1e-16):
    num = X.sum(axis=0) @ A
    return A * num / (num + eps), R


def mu_step_outside_mu(X, A, R, eps=1e-16):
    with jax.named_scope("products"):
        num = X.sum(axis=0) @ A
    return A * num / (num + eps), R
