SELECT i_brand_id, SUM(ss_ext_sales_price), COUNT(ss_quantity)
FROM date_dim, store_sales, item
WHERE ss_sold_date_sk = d_date_sk
  AND ss_item_sk = i_item_sk
  AND i_manager_id = {manager}
  AND d_moy = {month}
  AND d_year = {year}
GROUP BY i_brand_id
