"""The system under test for `resnet50`: mxtpu's ResNet symbol at the
configuration's sizes, and how a batch is drawn from the seed."""


def symbol(cfg, traffic):
    from mxtpu.models import resnet
    return resnet.get_symbol(num_classes=cfg["num_classes"],
                             num_layers=cfg["num_layers"],
                             image_shape=tuple(cfg["image_shape"]))


def items_per_row(cfg, traffic):
    return 1


def inputs(cfg, traffic, batch):
    """(data descs, label descs, draw): normalised-image noise in the
    configuration's dtype and labels over the classes; every row differs."""
    import jax
    import jax.numpy as jnp
    shape = (batch,) + tuple(cfg["image_shape"])

    def draw(key):
        k1, k2 = jax.random.split(key)
        return {"data": jax.random.normal(k1, shape, jnp.float32).astype(cfg["dtype"]),
                "softmax_label": jax.random.randint(
                    k2, (batch,), 0, cfg["num_classes"]).astype(jnp.float32)}

    return ([("data", shape, cfg["dtype"])],
            [("softmax_label", (batch,), "float32")], draw)
