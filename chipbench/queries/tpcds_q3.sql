SELECT i_brand_id, SUM({aggc}), COUNT(ss_quantity)
FROM date_dim, store_sales, item
WHERE ss_sold_date_sk = d_date_sk
  AND ss_item_sk = i_item_sk
  AND i_manufact_id = {manufact}
  AND d_moy = {month}
GROUP BY i_brand_id
