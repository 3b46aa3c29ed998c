"""One writer per dataset recipe, found by the recipe's name
(``"recipe"`` in a traffic file's ``dataset``): ``<name>.py`` with
``write(tmp, root, recipe, size, classes, names, num_classes)``, which writes
the pixels of image i (pattern class ``classes[i]``, manifest name
``names[i]``) under ``tmp`` — published as ``root`` once complete — and
``flags(root) -> dict``, the entry-point flags beyond the manifests and the
image directory. ``benchmark/datasets.py`` does everything the recipes share."""
