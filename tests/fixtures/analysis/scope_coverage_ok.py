"""Near misses: MU steps that open the "products" scope themselves, through
a helper, or through a module-level table of steps; and a factory whose
name merely contains the pattern (exempt by prefix)."""
import jax


def x_products(X, A):
    with jax.named_scope("products"):
        return X.sum(axis=0) @ A


@jax.named_scope("mu")
def mu_step_direct(X, A, R, eps=1e-16):
    with jax.named_scope("products"):
        num = X.sum(axis=0) @ A
    return A * num / (num + eps), R


def mu_step_with_block(X, A, R, eps=1e-16):
    with jax.named_scope("mu"):
        num = x_products(X, A)
        return A * num / (num + eps), R


SCHEDULES: dict = {"direct": mu_step_direct}


@jax.named_scope("mu")
def masked_mu_step(X, A, R, mask, schedule="direct"):
    A, R = SCHEDULES[schedule](X, A, R)
    return A * mask, R


def make_mu_step(cfg):
    def body(X, A, R):
        return mu_step_direct(X, A, R)
    return body
