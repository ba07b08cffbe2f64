"""Symbolic model builders (role parity:
example/image-classification/symbols/ in the reference)."""
from . import resnet
from .resnet import get_symbol as resnet_symbol
from .inception_v3 import get_symbol as inception_v3_symbol
from .alexnet import get_symbol as alexnet_symbol
from .granite_hybrid import granite_hybrid_symbol


def lenet(num_classes=10):
    """LeNet (reference example/image-classification/train_mnist.py model)."""
    from .. import symbol as sym
    data = sym.Variable("data")
    c1 = sym.Convolution(data=data, kernel=(5, 5), num_filter=20, name="conv1")
    t1 = sym.Activation(c1, act_type="tanh")
    p1 = sym.Pooling(t1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = sym.Convolution(p1, kernel=(5, 5), num_filter=50, name="conv2")
    t2 = sym.Activation(c2, act_type="tanh")
    p2 = sym.Pooling(t2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    f = sym.Flatten(p2)
    fc1 = sym.FullyConnected(f, num_hidden=500, name="fc1")
    t3 = sym.Activation(fc1, act_type="tanh")
    fc2 = sym.FullyConnected(t3, num_hidden=num_classes, name="fc2")
    return sym.SoftmaxOutput(fc2, name="softmax")


def mlp(num_classes=10, hidden=(128, 64)):
    """reference example/image-classification/train_mnist.py mlp."""
    from .. import symbol as sym
    net = sym.Variable("data")
    net = sym.Flatten(net)
    for i, h in enumerate(hidden):
        net = sym.FullyConnected(net, num_hidden=h, name="fc%d" % (i + 1))
        net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=num_classes,
                             name="fc%d" % (len(hidden) + 1))
    return sym.SoftmaxOutput(net, name="softmax")
